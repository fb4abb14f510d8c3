"""KV pool blocks held (%) of the pool, averaged over the window's steps."""


def reduce(ctx):
    s = ctx.window_steps()
    return 100.0 * sum(x.kv_used for x in s) / len(s) / ctx.pool_blocks if s else None
