"""Fused paged prefill attention kernel, online cells: roofline least time
over its device time (%)."""
from chipbench.lib.layer_metrics import prefill_kernel_share


def reduce(ctx):
    return prefill_kernel_share(ctx)
