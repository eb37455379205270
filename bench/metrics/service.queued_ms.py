"""Mean wait of a query in a shard's admission window (``queued_ms`` of
``service.execute``), over the window's executions (ms)."""
from bench.spans import flatten, mean


def read(ctx):
    return mean(
        float(s["attrs"].get("queued_ms", 0.0))
        for t in ctx.traces for s in flatten(t)
        if s["name"] == "service.execute"
    )
