"""Set-up time: from the process's start to the window's (imports, the
device, the system under test, and the warm-up of every program the
cell's traffic uses)."""


def value(ctx):
    return ctx.setup_s
