"""Seconds from process start to the start of the window: weights, engine,
compiles or cache loads, warm-up."""


def reduce(ctx):
    return ctx.setup_s
