"""Shared arithmetic of the per-layer metrics (`metrics/<name>.py`).

Each function returns None where the run gave it nothing to read: no
call of that program in the window, no trace, no event of that name. A
share of a roofline is never reported as 0 for want of data.
"""
from __future__ import annotations

from chipbench.lib import roofline, stats
from chipbench.lib.traffic import MEASURED

# program and kernel names as the TPU trace shows them (see PERF.md)
DECODE_PROGRAM = r"_serve_step_paged"
CHUNK_PROGRAM = r"_prefill_chunk_paged"
DECODE_KERNEL = r"^paged_decode_attention"
PREFILL_KERNEL = r"^paged_prefill_attention"


def ttft_ms(ctx, q: float):
    v = stats.ttfts([t for t in ctx.tracks if t.stream == MEASURED], ctx.t0, ctx.t1, ctx.stop)
    return stats.percentile(v, q) * 1e3 if v else None


def itl_ms(ctx, q: float):
    v = stats.gaps(ctx.tracks, ctx.t0, ctx.t1)
    return stats.percentile(v, q) * 1e3 if v else None


def _share(least_s: float, device_s: float):
    if device_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / device_s


def decode_step_share(ctx):
    if ctx.trace is None or not ctx.calls or not ctx.calls.decodes:
        return None
    least = sum(roofline.least_time(*roofline.decode_step(ctx.model, ln), ctx.peaks)
                for ln in ctx.calls.decodes)
    return _share(least, ctx.trace.module_time(DECODE_PROGRAM)[0])


def chunk_step_share(ctx):
    if ctx.trace is None or not ctx.calls or not ctx.calls.chunks:
        return None
    least = sum(roofline.least_time(*roofline.chunk_step(ctx.model, c, c0), ctx.peaks)
                for c, c0 in ctx.calls.chunks)
    return _share(least, ctx.trace.module_time(CHUNK_PROGRAM)[0])


def decode_kernel_share(ctx):
    if ctx.trace is None or not ctx.calls or not ctx.calls.decodes:
        return None
    layers = ctx.model["num_hidden_layers"]
    least = layers * sum(roofline.least_time(*roofline.decode_kernel(ctx.model, ln), ctx.peaks)
                         for ln in ctx.calls.decodes)
    return _share(least, ctx.trace.op_time(DECODE_KERNEL)[0])


def prefill_kernel_share(ctx):
    if ctx.trace is None or not ctx.calls or not ctx.calls.chunks:
        return None
    layers = ctx.model["num_hidden_layers"]
    least = layers * sum(roofline.least_time(*roofline.prefill_kernel(ctx.model, c, c0), ctx.peaks)
                         for c, c0 in ctx.calls.chunks)
    return _share(least, ctx.trace.op_time(PREFILL_KERNEL)[0])
