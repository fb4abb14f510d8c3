"""XLA compilations inside the measured window (JAX monitoring events)."""


def reduce(ctx):
    return ctx.compiles
