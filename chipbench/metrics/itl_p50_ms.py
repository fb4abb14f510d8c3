"""Median of all inter-token gaps (ms), over all requests, that close in
the window."""
from chipbench.lib.layer_metrics import itl_ms


def reduce(ctx):
    return itl_ms(ctx, 50)
