"""Whole prefill chunk step (prefill_chunk_paged), batch cells: roofline
least time over its device time (%)."""
from chipbench.lib.layer_metrics import chunk_step_share


def reduce(ctx):
    return chunk_step_share(ctx)
