"""1 - (union of device operation intervals / profiled window), the mean
over the cell's devices (%)."""
from bench import devtrace


def read(ctx):
    if ctx.device is None:
        return None
    lo, hi = ctx.device.window_ms
    busy = [devtrace.busy_ms(ctx.device, d) for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
