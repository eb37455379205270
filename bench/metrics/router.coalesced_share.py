"""Queries the router coalesced onto an execution already in flight, over
the queries it received in the window (%)."""


def read(ctx):
    queries = ctx.delta("queries")
    return 100.0 * ctx.delta("coalesced") / queries if queries else None
