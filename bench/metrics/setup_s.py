"""Process start to the window's start (s): generate, publish, load, warm."""


def read(ctx):
    return ctx.setup_s
