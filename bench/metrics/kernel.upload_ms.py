"""Mean per request of its ``kernel.upload`` spans (ms): the packed batch
put on the device, until it is there.  None where the program emits no
such span."""
from bench.spans import flatten, mean, per_request_sum

NAMES = {"kernel.upload"}


def read(ctx):
    if not any(s["name"] in NAMES for t in ctx.traces for s in flatten(t)):
        return None
    return mean(per_request_sum(ctx.traces, NAMES))
