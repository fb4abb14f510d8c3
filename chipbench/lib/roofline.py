"""Operations and bytes of the served programs, from their shapes.

Each function counts what the algorithm needs for one call, at the real
lengths of its sequences (not the padded table width): matmul and
attention FLOPs, weights read once, cached K/V read, new K/V written.
The least time of a call is the larger of FLOPs over the chip's peak
FLOP/s and bytes over its peak bytes/s; a roofline share is the least
time summed over calls, over the device time those calls took.
"""
from __future__ import annotations

from typing import Sequence

BF16 = 2
F32 = 4


def _dims(m: dict):
    L, d, f = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    h, kvh, hd, v = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["vocab_size"]
    return L, d, f, h, kvh, hd, v


def layer_matmul_params(m: dict) -> int:
    L, d, f, h, kvh, hd, v = _dims(m)
    return d * (h * hd + 2 * kvh * hd) + h * hd * d + 3 * d * f


def weight_bytes(m: dict) -> int:
    """Bytes of the weights a step reads once: every layer's matrices and
    norms, the final norm and the output head (the embedding is read by
    row, counted per token)."""
    L, d, f, h, kvh, hd, v = _dims(m)
    return BF16 * (L * layer_matmul_params(m) + v * d) + F32 * (2 * L * d + d)


def kv_bytes_per_token(m: dict) -> int:
    L, d, f, h, kvh, hd, v = _dims(m)
    return L * 2 * kvh * hd * BF16


def decode_step(m: dict, lengths: Sequence[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over sequences with `lengths`
    cached tokens each, one new token each."""
    L, d, f, h, kvh, hd, v = _dims(m)
    b = len(lengths)
    ctx = sum(lengths)
    flops = 2.0 * b * (L * layer_matmul_params(m) + v * d) + L * 4.0 * h * hd * (ctx + b)
    nbytes = (weight_bytes(m) + b * d * BF16 + ctx * kv_bytes_per_token(m)
              + b * kv_bytes_per_token(m) + b * v * BF16)
    return flops, float(nbytes)


def chunk_step(m: dict, tokens: int, ctx0: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of `tokens` tokens after `ctx0`
    cached ones; logits only at its last position."""
    L, d, f, h, kvh, hd, v = _dims(m)
    c = tokens
    pairs = c * ctx0 + c * (c + 1) / 2
    flops = 2.0 * c * L * layer_matmul_params(m) + 2.0 * v * d + L * 4.0 * h * hd * pairs
    nbytes = (weight_bytes(m) + c * d * BF16 + ctx0 * kv_bytes_per_token(m)
              + c * kv_bytes_per_token(m) + v * BF16)
    return flops, float(nbytes)


def decode_kernel(m: dict, lengths: Sequence[int]) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's paged decode attention call."""
    L, d, f, h, kvh, hd, v = _dims(m)
    b = len(lengths)
    ctx = sum(lengths)
    flops = 4.0 * h * hd * (ctx + b)
    nbytes = (ctx * 2 * kvh * hd * BF16          # cached K and V pages, real lengths
              + 2 * b * h * hd * BF16            # queries in, outputs out
              + 2 * b * kvh * hd * BF16)         # the step's own K and V
    return flops, float(nbytes)


def prefill_kernel(m: dict, tokens: int, ctx0: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's fused paged prefill attention call."""
    L, d, f, h, kvh, hd, v = _dims(m)
    c = tokens
    flops = 4.0 * h * hd * (c * ctx0 + c * (c + 1) / 2)
    nbytes = (ctx0 * 2 * kvh * hd * BF16
              + 2 * c * h * hd * BF16
              + 2 * c * kvh * hd * BF16)
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds the chip needs at best: the larger of its two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
