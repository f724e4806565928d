"""Host time from the guard vector's arrival to the next chunk's dispatch
call (``igg.guard_fetch`` end to ``igg.dispatch`` start: the driver's
commit and prepare, and its caller), per boundary (mean over the window's
boundaries; `benchmark/boundary.py`)."""

from benchmark import boundary


def read(ctx):
    return boundary.mean_ms(ctx, 1)
