"""Device time of the PT Stokes step's send-slab computes (the ops under
the program's ``igg.stokes.slabs`` scope; `benchmark/kernels.py`) as a
share of the device's compute time in the traced window (union of
non-collective op intervals), the largest over the chips. Nothing where
no op carries the scope."""

from benchmark import kernels
from benchmark import trace as TR

SCOPE = "igg.stokes.slabs"


def read(ctx):
    t = kernels.scope_ns(ctx, SCOPE)
    if t is None:
        return None
    shares = [s / c for s, c in
              ((s, TR.total(TR.compute(d, ctx.window)))
               for s, d in zip(t, ctx.devices)) if c > 0]
    return 100.0 * max(shares) if shares else None
