"""95th percentile, over the chunks of the traced window, of the device
time from one start of the chunk program to the next: the chunk's own
execution plus the stall at its boundary. The largest over the chips."""

from benchmark import trace as TR


def _p95(values):
    import statistics

    return statistics.quantiles(values, n=20, method="inclusive")[18]


def read(ctx):
    out = []
    for d in ctx.devices:
        starts = [s for s, _ in TR.chunk_runs(d, ctx.window)]
        gaps = [b - a for a, b in zip(starts, starts[1:])]
        if len(gaps) >= 2:
            out.append(_p95(gaps) / 1e6)
    return max(out) if out else None
