"""Requests answered per second over the whole window: from its start to
the last answer."""


def read(ctx):
    answered = sum(1 for r in ctx.records if r["status"] == 200)
    return answered / ctx.window_s()
