"""Public jit'd wrappers around the Pallas kernels.

These adapt the model-code layouts ((B, S, H, D) activations) to the
kernels' heads-major layouts, select interpret mode automatically off-TPU
(the kernels' *target* is TPU; interpret=True executes the kernel body in
Python for CPU validation), and guard shapes/dtypes.

Block selection: attention block sizes default to `vmem.autotune_block` -
the largest power-of-two tile whose estimated working set fits the 16 MiB
VMEM budget for this head_dim/group - then shrink to divide the actual
sequence. Pass block_q/block_k explicitly to override.

The paged ops (`paged_decode_attention`, `paged_prefill_attention`)
additionally take an `impl` switch: "pallas" runs the TPU kernel
(interpret mode off-TPU - the CI numerics path), "jnp" runs a pure-jnp
twin whose operations mirror models/attention.py's dense math exactly
(same dtype casts, same masked-softmax shape), so the engine's paged hot
path is *bit-identical* to the dense gather path on CPU. "auto" picks
pallas on TPU and jnp elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import vmem
from repro.kernels.decode_attention import decode_attention_grouped
from repro.kernels.flash_attention import flash_attention_hsd
from repro.kernels.mamba2_ssd import mamba2_ssd_htp
from repro.kernels.paged_attention import (
    paged_decode_attention_grouped,
    paged_prefill_attention_fused,
)
from repro.kernels.rwkv6_wkv import rwkv6_wkv_htn

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two block <= target that divides s."""
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


@functools.lru_cache(maxsize=None)
def _flash_block_default(head_dim: int) -> int:
    return vmem.autotune_block(
        lambda b: vmem.flash_attention_vmem(b, b, head_dim), lo=128, hi=2048)


@functools.lru_cache(maxsize=None)
def _decode_block_default(group: int, head_dim: int) -> int:
    return vmem.autotune_block(
        lambda b: vmem.decode_attention_vmem(group, b, head_dim),
        lo=128, hi=4096)


def resolve_impl(impl: str) -> str:
    """"auto" -> "pallas" on a TPU, "jnp" elsewhere; else `impl` itself."""
    if impl == "auto":
        return "jnp" if _interpret() else "pallas"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"impl must be auto|pallas|jnp: {impl!r}")
    return impl


def flash_attention(q, k, v, causal: bool = True, block_q: "int | None" = None,
                    block_k: "int | None" = None):
    """q: (B, S, H, D), k/v: (B, S, KV, D) -> (B, S, H, D)."""
    assert q.ndim == 4 and k.shape[:2] == q.shape[:2], (q.shape, k.shape)
    s, d = q.shape[1], q.shape[3]
    if block_q is None or block_k is None:
        tuned = _flash_block_default(d)
        block_q = tuned if block_q is None else block_q
        block_k = tuned if block_k is None else block_k
    out = flash_attention_hsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        block_q=_pick_block(s, block_q),
        block_k=_pick_block(s, block_k),
        interpret=_interpret(),
    )
    return out.transpose(0, 2, 1, 3)


def decode_attention(q, k_cache, v_cache, pos, block_k: "int | None" = None):
    """q: (B, 1, H, D), caches: (B, KV, S, D), pos: (B,) -> (B, 1, H, D)."""
    b, _, h, d = q.shape
    kvh = k_cache.shape[1]
    g = h // kvh
    qg = q[:, 0].reshape(b, kvh, g, d)
    s = k_cache.shape[2]
    if block_k is None:
        block_k = _decode_block_default(g, d)
    out = decode_attention_grouped(
        qg, k_cache, v_cache, pos.astype(jnp.int32),
        block_k=_pick_block(s, block_k), interpret=_interpret(),
    )
    return out.reshape(b, 1, h, d)


# ---------------------------------------------------------------------------
# paged attention (PagedKVPool-native)
# ---------------------------------------------------------------------------
def paged_decode_attention(q, k_pages, v_pages, layer, tables, lengths, k_new,
                           v_new, max_len: int, impl: str = "auto"):
    """One decode step straight off the paged pool (gather-free).

    q: (B, 1, H, D); k_pages/v_pages: (L, NBp, KV, bs, D) - the whole of
    `PagedKVPool.k/v`; layer: () int32, the pool layer to read; tables:
    (B, NB) int32 dump-padded block tables; lengths: (B,) cached tokens per
    sequence; k_new/v_new: (B, 1, KV, D) the step's own K/V (post-RoPE, not
    yet in the pool); max_len: static batch-max sequence length INCLUDING
    the new token -> (B, 1, H, D)."""
    b, _, h, d = q.shape
    kvh, bs = k_pages.shape[2], k_pages.shape[3]
    g = h // kvh
    impl = resolve_impl(impl)
    if impl == "jnp":
        return _paged_decode_jnp(q, k_pages[layer], v_pages[layer], tables,
                                 lengths, k_new, v_new, max_len)
    # page groups from the shapes; VMEM guard: the query group sits next to
    # two slots of page groups
    nb = tables.shape[1]
    pages = vmem.paged_decode_pages(kvh, g, bs, d, nb)
    compute_pages = vmem.paged_decode_compute_pages(bs, nb)
    vmem.paged_decode_vmem(kvh, g, bs, d, pages, compute_pages).assert_fits(
        "paged_decode_attention")
    qg = q[:, 0].reshape(b, kvh, g, d)
    out = paged_decode_attention_grouped(
        qg, k_pages, v_pages, layer, tables, lengths.astype(jnp.int32),
        k_new.transpose(0, 2, 1, 3), v_new.transpose(0, 2, 1, 3),
        pages=pages, compute_pages=compute_pages, interpret=_interpret(),
    )
    return out.reshape(b, 1, h, d)


def _paged_decode_jnp(q, k_pages, v_pages, tables, lengths, k_new, v_new,
                      max_len: int):
    """jnp twin: operation-for-operation the dense decode path
    (models/attention.py attention_decode_block + decode_attention) applied
    to the page-gathered cache, so its logits are bit-identical to the
    gather engine path. The ragged-length mask is what hides the
    dump-block garbage past each sequence's blocks - see kv_cache.py."""
    b, _, h, d = q.shape
    kvh, bs = k_pages.shape[1], k_pages.shape[2]
    g = h // kvh
    nb = tables.shape[1]

    def densify(pages):
        got = pages[tables]                            # (B, NB, KV, bs, D)
        return jnp.moveaxis(got, 2, 1).reshape(b, kvh, nb * bs, d)[:, :, :max_len]

    def write(cache, new, p):
        return jax.lax.dynamic_update_slice(cache, new, (0, p, 0))

    kc = jax.vmap(write)(densify(k_pages), k_new.transpose(0, 2, 1, 3), lengths)
    vc = jax.vmap(write)(densify(v_pages), v_new.transpose(0, 2, 1, 3), lengths)
    qh = q[:, 0].reshape(b, kvh, g, d)
    scores = jnp.einsum("bqgd,bqtd->bqgt", qh, kc).astype(jnp.float32) * (d ** -0.5)
    mask = jnp.arange(max_len)[None, :] <= lengths[:, None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bqgt,bqtd->bqgd", probs, vc)
    return out.reshape(b, 1, h, d)


def paged_prefill_attention(q, k_pages, v_pages, table, ctx: int,
                            k_self, v_self, impl: str = "auto"):
    """One prefill chunk of ONE sequence vs its paged context + itself.

    q: (1, C, H, D); k_pages/v_pages: (NBp, KV, bs, D) - one pool layer;
    table: (NB,) int32 block table covering the `ctx` cached tokens
    (dump-padded; may be empty when ctx == 0); ctx: static cached token
    count; k_self/v_self: (1, C, KV, D) the chunk's own K/V (post-RoPE)
    -> (1, C, H, D)."""
    _, c, h, d = q.shape
    kvh, bs = k_pages.shape[1], k_pages.shape[2]
    g = h // kvh
    impl = resolve_impl(impl)
    if table.shape[0] == 0:
        table = jnp.full((1,), k_pages.shape[0] - 1, jnp.int32)  # dump page
    if impl == "jnp":
        # twin of the dense prefill math: one _attend_block over
        # [gathered context ; chunk] with the chunk's global offset -
        # bit-identical to the recompute path's rows (see docs/kernels.md)
        from repro.models.attention import _attend_block

        nb = table.shape[0]

        def densify(pages):
            got = pages[table]                          # (NB, KV, bs, D)
            return got.transpose(0, 2, 1, 3).reshape(nb * bs, kvh, d)[:ctx]

        kc = jnp.concatenate([densify(k_pages), k_self[0]], axis=0)[None]
        vc = jnp.concatenate([densify(v_pages), v_self[0]], axis=0)[None]
        return _attend_block(q, kc, vc, jnp.int32(ctx), True)
    # VMEM guard: all chunk query rows stay resident per program; the
    # autotuned ceiling bounds usable BatchPolicy.chunk_tokens (docs/kernels.md)
    est = vmem.paged_prefill_vmem(c * g, c, bs, d)
    if not est.fits:
        raise ValueError(
            f"chunk of {c} tokens x group {g} = {c * g} query rows needs "
            f"{est.total_bytes / 2**20:.2f} MiB VMEM (> "
            f"{vmem.VMEM_BYTES / 2**20:.0f} MiB); lower BatchPolicy.chunk_tokens")
    qg = q[0].reshape(c, kvh, g, d).transpose(1, 0, 2, 3).reshape(kvh, c * g, d)
    out = paged_prefill_attention_fused(
        qg, k_pages, v_pages, table, jnp.asarray(ctx, jnp.int32),
        k_self[0].transpose(1, 0, 2), v_self[0].transpose(1, 0, 2),
        group=g, interpret=_interpret(),
    )
    return out.reshape(kvh, c, g, d).transpose(1, 0, 2, 3).reshape(1, c, h, d)


def rwkv6_wkv(r, k, v, logw, u, state0=None, chunk: int = 16):
    """Model layout (B, T, H, N) -> kernel layout (B, H, T, N) and back."""
    b, t, h, n = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, n, n), jnp.float32)
    tr = lambda a: a.transpose(0, 2, 1, 3)
    y, state = rwkv6_wkv_htn(
        tr(r), tr(k), tr(v), tr(logw.astype(jnp.float32)),
        u.astype(jnp.float32), state0,
        chunk=min(chunk, t) if t % chunk == 0 else _pick_block(t, chunk),
        interpret=_interpret(),
    )
    return tr(y), state


def mamba2_ssd(xh, b_in, c_in, dt, a_log, state0=None, chunk: int = 128):
    """Model layout xh (B, T, H, P) -> kernel layout and back.

    NOTE kernel state layout is (B, H, N, P) matching models/mamba2.py."""
    b, t, h, p = xh.shape
    n = b_in.shape[-1]
    if state0 is None:
        state0 = jnp.zeros((b, h, n, p), jnp.float32)
    y, state = mamba2_ssd_htp(
        xh.transpose(0, 2, 1, 3), b_in, c_in,
        dt.astype(jnp.float32).transpose(0, 2, 1), a_log, state0,
        chunk=_pick_block(t, chunk), interpret=_interpret(),
    )
    return y.transpose(0, 2, 1, 3), state
