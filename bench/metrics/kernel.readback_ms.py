"""Mean per request of its ``kernel.readback`` spans (ms): the search's ids
and mask copied to the host.  None where the program emits no such
span."""
from bench.spans import flatten, mean, per_request_sum

NAMES = {"kernel.readback"}


def read(ctx):
    if not any(s["name"] in NAMES for t in ctx.traces for s in flatten(t)):
        return None
    return mean(per_request_sum(ctx.traces, NAMES))
