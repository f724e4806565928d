"""Time from the device finishing a chunk program run to the host holding
its guard vector (the end of the driver's ``igg.guard_fetch`` span), per
boundary (mean over the window's boundaries; `benchmark/boundary.py`)."""

from benchmark import boundary


def read(ctx):
    return boundary.mean_ms(ctx, 0)
