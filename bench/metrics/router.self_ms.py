"""Mean per request of the self time of ``router.submit``,
``shard.gather`` and ``router.merge`` (ms); a query's wait in a shard's
admission window counts as the service's (``service.queued_ms``)."""
from bench.spans import mean, per_request_sum, self_ms

NAMES = {"router.submit", "shard.gather", "router.merge"}


def read(ctx):
    return mean(per_request_sum(ctx.traces, NAMES, self_ms))
