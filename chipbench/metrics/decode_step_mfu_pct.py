"""Whole decode step (serve_step_paged): roofline least time over its
device time (%)."""
from chipbench.lib.layer_metrics import decode_step_share


def reduce(ctx):
    return decode_step_share(ctx)
