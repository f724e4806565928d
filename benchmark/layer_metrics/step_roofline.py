"""Share of the HBM roofline of the model step: the algorithmic bytes of
the steps committed in the traced window, over the device's compute time
(union of non-collective op intervals, mean over chips), over the device
kind's published HBM bandwidth. The bytes are the least the step needs,
so a reading over 100% means the timing misses part of the work."""

from benchmark import trace as TR


def read(ctx):
    t = sum(TR.total(TR.compute(d, ctx.window))
            for d in ctx.devices) / len(ctx.devices) / 1e9
    if not t or not ctx.steps or not ctx.hbm_peak:
        return None
    return 100.0 * ctx.bytes_per_step * ctx.steps / t / ctx.hbm_peak
