"""Paged decode attention kernel: roofline least time over its device time
(%)."""
from chipbench.lib.layer_metrics import decode_kernel_share


def reduce(ctx):
    return decode_kernel_share(ctx)
