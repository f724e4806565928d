"""Share of the HBM roofline of the fused PT Stokes pass alone: the
algorithmic bytes of the steps committed in the traced window (each
updated field's local array read and written once, ``rhog`` read once: 15
arrays), over the device time of the ops under the program's
``igg.stokes.pt`` scope (mean over chips; `benchmark/kernels.py`), over
the device kind's published HBM bandwidth. Nothing where no op carries
the scope."""

from benchmark import kernels

SCOPE = "igg.stokes.pt"


def read(ctx):
    t = kernels.scope_ns(ctx, SCOPE)
    if not t or not ctx.steps or not ctx.hbm_peak:
        return None
    mean_s = sum(t) / len(t) / 1e9
    return 100.0 * ctx.bytes_per_step * ctx.steps / mean_s / ctx.hbm_peak
