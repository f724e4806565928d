"""Device idle time between the end of one chunk program's execution and
the start of the next, per boundary (mean over boundaries and chips): what
the chip waits on the supervised driver's host work at each commit."""

from benchmark import trace as TR


def read(ctx):
    gaps = [g for d in ctx.devices for g in TR.boundary_idle(d, ctx.window)]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
