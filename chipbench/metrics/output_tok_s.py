"""Output tokens emitted in the window, over all requests, per second of
the window."""
from chipbench.lib.stats import tokens_in


def reduce(ctx):
    return tokens_in(ctx.tracks, ctx.t0, ctx.t1) / (ctx.t1 - ctx.t0)
