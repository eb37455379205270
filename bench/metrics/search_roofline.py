"""Share of the memory roofline the search launches reach (%).

Numerator: the least time the chip could take for the launches packed in
the profiled window, their compulsory bytes (``bench.roofline``, from each
``plan.pack`` span's shape) over the chip's HBM bandwidth.  Denominator:
the time the device was busy in the window, the union of its operation
intervals (operations nest on the trace's op line, so their sum would
count a loop and its body twice); only the search runs on the device."""
from bench import devtrace
from bench.roofline import peaks, search_bytes
from bench.spans import launches


def read(ctx):
    if ctx.device is None:
        return None
    lo, hi = ctx.device.window_ms
    nbytes = sum(
        search_bytes(s["attrs"]["rows"], s["attrs"]["k"], s["attrs"]["m0"],
                     s["attrs"]["mo"])
        for s in launches(ctx.traces) if lo <= s["t0_ms"] < hi
    )
    busy_s = sum(devtrace.busy_ms(ctx.device, d) for d in ctx.devices) / 1e3
    if not nbytes or not busy_s:
        return None
    return 100.0 * nbytes / peaks(ctx.device_kind)["hbm_bw"] / busy_s
