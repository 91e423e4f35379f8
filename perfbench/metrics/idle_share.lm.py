"""Share of the traced window in which no operation ran on the device,
mean over the cell's devices."""
from perfbench import traceio


def read(ctx):
    w = ctx.trace.window[1] - ctx.trace.window[0]
    busy = traceio.mean_over_devices(
        ctx.trace, ctx.device_ids, lambda d: traceio.busy_s(ctx.trace, d))
    return 100.0 * (1.0 - busy / w)
