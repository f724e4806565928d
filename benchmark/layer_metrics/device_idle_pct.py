"""Share of the traced window in which no operation ran on the device
(mean over chips): 1 - union of busy op intervals / window."""

from benchmark import trace as TR


def read(ctx):
    w = ctx.window
    if w.length <= 0:
        return None
    busy = sum(TR.total(TR.busy(d, w)) for d in ctx.devices) / len(
        ctx.devices)
    return 100.0 * (1.0 - busy / w.length)
