"""Seconds the program spent tracing, lowering, compiling and loading from
the persistent compilation cache before the window (the process counter
``compile_s``).  None where the program keeps no such counter."""


def read(ctx):
    if "compile_s" not in ctx.stats0:
        return None
    return float(ctx.stats0["compile_s"])
