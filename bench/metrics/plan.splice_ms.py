"""Mean per request of its ``plan.splice`` spans (ms): dummy results
resolved through the RCPM into the query's answer.  None where the
program emits no such span."""
from bench.spans import flatten, mean, per_request_sum

NAMES = {"plan.splice"}


def read(ctx):
    if not any(s["name"] in NAMES for t in ctx.traces for s in flatten(t)):
        return None
    return mean(per_request_sum(ctx.traces, NAMES))
