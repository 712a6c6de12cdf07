"""Device idle share in the compile cells: 1 - union of device-op
intervals over the traced window, averaged over the chips (profiler
trace)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_percent()
