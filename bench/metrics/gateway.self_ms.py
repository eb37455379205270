"""Mean per request of the self time of ``gateway.request`` (ms)."""
from bench.spans import mean, per_request_sum, self_ms


def read(ctx):
    return mean(per_request_sum(ctx.traces, {"gateway.request"}, self_ms))
