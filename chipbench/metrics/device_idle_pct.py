"""Share of the traced window (%) in which no operation ran on the device."""


def reduce(ctx):
    return None if ctx.trace is None else 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
