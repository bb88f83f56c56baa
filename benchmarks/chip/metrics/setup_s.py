"""Set-up: process start to the window's start (imports, weights, engine
build, warm-up and any compilation), host clock."""


def read(ctx):
    return ctx.setup_s
