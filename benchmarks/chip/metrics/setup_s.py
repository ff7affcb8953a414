"""Set-up: process start to the window's start (imports, data from the
seed, index build where the cell has one, compiles or cache loads, warm-up)."""


def read(ctx):
    return ctx.setup_s
