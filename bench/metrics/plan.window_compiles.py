"""XLA compiles in the window (the process counter ``compiles``): 0 when
the warm-up compiled every shape the window meets.  None where the
program keeps no such counter."""


def read(ctx):
    if "compiles" not in ctx.stats1:
        return None
    return ctx.delta("compiles")
