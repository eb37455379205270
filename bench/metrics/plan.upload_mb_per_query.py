"""Bytes the window's launches uploaded (``PlanCache`` counter
``upload_bytes``), per request of the window, in MB.  None where the
program keeps no such counter."""


def read(ctx):
    if "upload_bytes" not in ctx.stats1:
        return None
    return ctx.delta("upload_bytes") / len(ctx.records) / 1e6
