"""Static VMEM budgeting for the Pallas kernels.

TPU cores have ~16 MiB of VMEM; a kernel whose per-program working set
(input/output tiles + scratch) exceeds the budget fails at Mosaic compile
time on hardware. These estimators mirror each kernel's BlockSpec tiling
so block sizes can be validated/autotuned off-device (CPU interpret mode
never enforces the limit - this module does).
"""
from __future__ import annotations

import dataclasses

VMEM_BYTES = 16 * 2 ** 20
# double-buffering of HBM->VMEM streams: Mosaic keeps 2 copies of each
# streamed input tile in flight
STREAM_COPIES = 2


@dataclasses.dataclass(frozen=True)
class VmemEstimate:
    tiles_bytes: int
    scratch_bytes: int

    @property
    def total_bytes(self) -> int:
        return self.tiles_bytes + self.scratch_bytes

    @property
    def fits(self) -> bool:
        return self.total_bytes <= VMEM_BYTES

    def assert_fits(self, name: str) -> None:
        if not self.fits:
            raise ValueError(
                f"{name}: VMEM working set {self.total_bytes/2**20:.2f} MiB "
                f"exceeds the {VMEM_BYTES/2**20:.0f} MiB budget")


def flash_attention_vmem(block_q: int, block_k: int, head_dim: int,
                         dtype_bytes: int = 2) -> VmemEstimate:
    """q tile + k/v tiles (streamed, double-buffered) + out tile + scratch."""
    q = block_q * head_dim * dtype_bytes
    kv = 2 * STREAM_COPIES * block_k * head_dim * dtype_bytes
    out = block_q * head_dim * dtype_bytes
    scratch = (2 * block_q + block_q * head_dim) * 4          # m, l, acc fp32
    scores = block_q * block_k * 4                            # fp32 intermediates
    return VmemEstimate(q + kv + out + scores, scratch)


def decode_attention_vmem(group: int, block_k: int, head_dim: int,
                          dtype_bytes: int = 2) -> VmemEstimate:
    q = group * head_dim * dtype_bytes
    kv = 2 * STREAM_COPIES * block_k * head_dim * dtype_bytes
    out = group * head_dim * dtype_bytes
    scratch = (2 * group + group * head_dim) * 4
    scores = group * block_k * 4
    return VmemEstimate(q + kv + out + scores, scratch)


# tokens of one sequence the paged decode kernel scores in one pass: its
# time follows the number of these blocks more than the DMA group size
DECODE_COMPUTE_TOKENS = 512


def paged_decode_compute_pages(block_size: int, table_width: int) -> int:
    """Pages the paged decode kernel scores at once, never above the table."""
    return max(1, min(DECODE_COMPUTE_TOKENS // block_size, table_width))


def paged_decode_vmem(num_kv_heads: int, group: int, block_size: int,
                      head_dim: int, pages: int, compute_pages: int,
                      dtype_bytes: int = 2) -> VmemEstimate:
    """Paged decode: one sequence's program holds its kv heads' G query
    rows, the step's new K/V and its output (each pipelined), two slots of
    `pages` pool pages of K and of V (its manual DMA groups), and one
    head's fp32 V, scores and probabilities over `compute_pages` pages."""
    rows = num_kv_heads * group * head_dim * dtype_bytes     # q block, out block
    new = 2 * num_kv_heads * head_dim * dtype_bytes          # k_new, v_new blocks
    tiles = STREAM_COPIES * (2 * rows + new)
    page = num_kv_heads * block_size * head_dim * dtype_bytes
    groups = 2 * STREAM_COPIES * pages * page                # K and V, two slots
    span = compute_pages * block_size
    work = span * head_dim * 4 + 2 * group * span * 4
    scratch = num_kv_heads * (2 * group + group * head_dim) * 4   # m, l, acc fp32
    return VmemEstimate(tiles + groups + work, scratch)


def paged_decode_pages(num_kv_heads: int, group: int, block_size: int,
                       head_dim: int, table_width: int,
                       dtype_bytes: int = 2) -> int:
    """Pages per DMA group of the paged decode kernel: enough whole compute
    blocks to cover the table, or the most whose working set still fits."""
    c = paged_decode_compute_pages(block_size, table_width)
    n = -(-table_width // c)
    while n > 1 and not paged_decode_vmem(num_kv_heads, group, block_size, head_dim,
                                          n * c, c, dtype_bytes).fits:
        n -= 1
    return n * c


def paged_prefill_vmem(rows: int, chunk: int, block_size: int, head_dim: int,
                       dtype_bytes: int = 2) -> VmemEstimate:
    """Fused chunked prefill: `rows` = chunk_tokens * group query rows per
    kv head stay resident; context pages stream; the chunk's own K/V
    (`chunk` tokens) is held whole for the causal self step."""
    q = rows * head_dim * dtype_bytes
    kv = 2 * STREAM_COPIES * block_size * head_dim * dtype_bytes
    self_kv = 2 * chunk * head_dim * dtype_bytes
    out = rows * head_dim * dtype_bytes
    scratch = (2 * rows + rows * head_dim) * 4
    scores = rows * max(block_size, chunk) * 4
    return VmemEstimate(q + kv + self_kv + out + scores, scratch)


def rwkv6_vmem(chunk: int, n: int) -> VmemEstimate:
    tiles = 4 * STREAM_COPIES * chunk * n * 4 + chunk * n * 4  # r/k/v/w in, y out
    tiles += n * 4 + n * n * 4                                 # u, s0
    scores = chunk * chunk * 4
    scratch = n * n * 4                                        # state
    return VmemEstimate(tiles + scores, scratch)


def mamba2_vmem(chunk: int, n: int, p: int) -> VmemEstimate:
    tiles = STREAM_COPIES * (chunk * p + 2 * chunk * n + chunk) * 4
    tiles += chunk * p * 4 + n * p * 4                         # y out, s0
    scores = chunk * chunk * 4
    scratch = n * p * 4
    return VmemEstimate(tiles + scores, scratch)


def autotune_block(fits_fn, lo: int = 128, hi: int = 4096) -> int:
    """Largest power-of-two block in [lo, hi] whose estimate fits VMEM."""
    best = 0
    b = lo
    while b <= hi:
        if fits_fn(b).fits:
            best = b
        b *= 2
    if best == 0:
        raise ValueError("no block size fits VMEM")
    return best
