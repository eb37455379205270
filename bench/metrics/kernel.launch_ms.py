"""Mean per request of its ``kernel.ca_search`` and ``kernel.fused_round``
spans (ms): host clock around each launch, upload, device and read-back."""
from bench.spans import mean, per_request_sum

NAMES = {"kernel.ca_search", "kernel.fused_round"}


def read(ctx):
    return mean(per_request_sum(ctx.traces, NAMES))
