"""Share (%) of the prompt tokens handed over in the window that the
prefix cache served (`PrefixCache.stats()["hit_tokens"]`)."""


def reduce(ctx):
    if ctx.prefix_hit_tokens is None or not ctx.prompt_tokens:
        return None
    return 100.0 * ctx.prefix_hit_tokens / ctx.prompt_tokens
