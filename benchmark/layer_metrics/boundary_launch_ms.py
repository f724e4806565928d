"""Time from the driver's dispatch call (``igg.dispatch`` start) to the
device starting the next chunk program run, per boundary (mean over the
window's boundaries; `benchmark/boundary.py`)."""

from benchmark import boundary


def read(ctx):
    return boundary.mean_ms(ctx, 2)
