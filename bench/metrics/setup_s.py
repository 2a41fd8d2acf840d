"""Set-up: process start to the window's start, compiles included."""


def read(ctx):
    return ctx.setup_s
