"""Edge-cache hits over lookups in the window (%)."""


def read(ctx):
    hits = ctx.cache1["hits"] - ctx.cache0["hits"]
    lookups = hits + ctx.cache1["misses"] - ctx.cache0["misses"]
    return 100.0 * hits / lookups if lookups else None
