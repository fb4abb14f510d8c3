"""90th percentile of time to first token (ms) over every request due in
the window, from its due time; one with no first token by the end counts
what it waited."""
from chipbench.lib.layer_metrics import ttft_ms


def reduce(ctx):
    return ttft_ms(ctx, 90)
