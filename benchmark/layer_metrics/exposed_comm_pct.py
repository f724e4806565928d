"""Collective time with no compute on the same device, as a share of the
traced window; the largest over the chips."""

from benchmark import trace as TR


def read(ctx):
    w = ctx.window
    if w.length <= 0 or not any(d.comm for d in ctx.devices):
        return None
    return 100.0 * max(TR.exposed_comm(d, w) for d in ctx.devices) / w.length
