"""Mean per request of the self time of its ``plan.round`` spans (ms): a
frontier round's host work outside its pack and launch (posting-list
gather, nested-RC probe, memo claims).  None where the program emits no
such span."""
from bench.spans import flatten, mean, per_request_sum, self_ms

NAMES = {"plan.round"}


def read(ctx):
    if not any(s["name"] in NAMES for t in ctx.traces for s in flatten(t)):
        return None
    return mean(per_request_sum(ctx.traces, NAMES, self_ms))
