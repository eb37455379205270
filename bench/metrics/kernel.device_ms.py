"""Mean per request of its ``kernel.device`` spans (ms): from the search's
dispatch until its ids and mask are ready on the device.  None where the
program emits no such span."""
from bench.spans import flatten, mean, per_request_sum

NAMES = {"kernel.device"}


def read(ctx):
    if not any(s["name"] in NAMES for t in ctx.traces for s in flatten(t)):
        return None
    return mean(per_request_sum(ctx.traces, NAMES))
