"""Mean per request of its ``plan.pack`` spans (ms): host packing of
posting lists into bucketed launch shapes.  A request answered by the edge
cache packs nothing."""
from bench.spans import mean, per_request_sum


def read(ctx):
    return mean(per_request_sum(ctx.traces, {"plan.pack"}))
