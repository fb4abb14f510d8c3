"""Sequences per decode step in the window (the scheduler's batch)."""


def reduce(ctx):
    d = ctx.calls.decodes if ctx.calls else []
    return sum(len(x) for x in d) / len(d) if d else None
