"""Attention: blocked causal prefill/train attention + single-token decode.

Memory discipline is the point here: a 32k-token prefill must never
materialize the full (B, H, S, S) score tensor. The blocked form iterates
over query blocks; each step materializes only (B, H, q_block, S) scores.
In scan mode the Q-block loop is a `lax.scan` with a checkpointed body so
that the *backward* pass also stays O(q_block) (flash-style recompute); in
static_unroll (cost) mode it is a Python loop with *static causal slicing*
of K/V so HLO FLOPs reflect the causal ~S^2/2 work.

The Pallas flash-attention kernel (kernels/flash_attention.py) implements
the same contract for the TPU hot path; `exec_cfg.kernels` routes to it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import AttentionConfig, ModelConfig
from repro.models.layers import ExecConfig, DEFAULT_EXEC, apply_rope, rope_angles

NEG_INF = -1e30


def init_attention(rng: jax.Array, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    a = cfg.attn
    d = d_model or cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    kq, kk, kv, ko = jax.random.split(rng, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(kq, (d, a.q_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d, a.kv_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d, a.kv_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (a.q_dim, d)) * (a.q_dim ** -0.5)).astype(dtype),
    }


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, H, D), k: (B, Sk, KV, D) -> scores (B, KV, H/KV, Sq, Sk)."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, sq, kvh, h // kvh, d)
    return jnp.einsum("bsqgd,btqd->bqgst", q, k) * (d ** -0.5)


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B, KV, G, Sq, Sk), v: (B, Sk, KV, D) -> (B, Sq, H, D)."""
    b, kvh, g, sq, _ = probs.shape
    o = jnp.einsum("bqgst,btqd->bsqgd", probs, v)
    return o.reshape(b, sq, kvh * g, -1)


def _attend_block(
    q: jax.Array,            # (B, qb, H, D)
    k: jax.Array,            # (B, Sk, KV, D)
    v: jax.Array,
    q_offset: jax.Array,     # scalar: global position of q[0]
    causal: bool,
) -> jax.Array:
    scores = _gqa_scores(q, k).astype(jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = jnp.arange(k.shape[1])
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v)


def multihead_attention(
    q: jax.Array,            # (B, S, H, D)  (already RoPE'd)
    k: jax.Array,            # (B, S, KV, D)
    v: jax.Array,
    cfg_attn: AttentionConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> jax.Array:
    """Full-sequence causal attention, blocked over query blocks."""
    b, s, h, d = q.shape
    qb = min(exec_cfg.q_block, s)
    if exec_cfg.kernels:
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, causal=cfg_attn.causal)
    if s <= qb:
        return _attend_block(q, k, v, jnp.int32(0), cfg_attn.causal)
    if s % qb:
        # pad queries to a block multiple; padded rows are discarded
        pad = qb - s % qb
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return multihead_attention(qp, k, v, cfg_attn, exec_cfg)[:, :s]
    nblocks = s // qb

    if exec_cfg.static_unroll:
        # Python loop + static causal slicing of K/V: HLO carries the true
        # causal FLOP count (~S^2/2) for the cost dry-run.
        outs = []
        for i in range(nblocks):
            hi = (i + 1) * qb
            outs.append(
                _attend_block(
                    q[:, i * qb : hi],
                    k[:, :hi],
                    v[:, :hi],
                    jnp.int32(i * qb),
                    cfg_attn.causal,
                )
            )
        return jnp.concatenate(outs, axis=1)

    qblocks = q.reshape(b, nblocks, qb, h, d).swapaxes(0, 1)  # (nb, B, qb, H, D)

    def body(carry, inp):
        i, qi = inp
        out = _attend_block(qi, k, v, i * qb, cfg_attn.causal)
        return carry, out

    body = jax.checkpoint(body)  # flash-style: recompute scores in backward
    _, outs = jax.lax.scan(body, None, (jnp.arange(nblocks), qblocks))
    return outs.swapaxes(0, 1).reshape(b, s, h, d)


def decode_attention(
    q: jax.Array,            # (B, 1, H, D)
    k_cache: jax.Array,      # (B, KV, S_max, D)
    v_cache: jax.Array,
    pos: jax.Array,          # (B,) current lengths (q is at index pos)
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> jax.Array:
    """Single-token attention against a (padded) KV cache."""
    if exec_cfg.kernels:
        from repro.kernels import ops as kops

        return kops.decode_attention(q, k_cache, v_cache, pos)
    b, kvh, smax, d = k_cache.shape
    h = q.shape[2]
    g = h // kvh
    qh = q[:, 0].reshape(b, kvh, g, d)
    scores = jnp.einsum("bqgd,bqtd->bqgt", qh, k_cache).astype(jnp.float32) * (d ** -0.5)
    # Ragged-length mask: the cache is padded to the batch max (S_max), so
    # for every sequence shorter than S_max the tail slots hold arbitrary
    # *finite* garbage (stale tokens, zeros, or - on the paged path - the
    # pool's dump block). This mask is the ONLY thing excluding those slots:
    # NEG_INF substitution before the softmax drives their probability to
    # exactly 0.0 regardless of content. Garbage must stay finite (never
    # NaN): 0.0 * NaN = NaN would still poison the value einsum below.
    mask = jnp.arange(smax)[None, :] <= pos[:, None]              # (B, S)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bqgt,bqtd->bqgd", probs, v_cache)
    return out.reshape(b, 1, h, d)


def attention_block(
    p: dict,
    x: jax.Array,             # (B, S, D_model)
    positions: jax.Array,     # (B, S) or (3, B, S) for m-rope
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Projections + RoPE + causal attention. Returns (out, (k, v)) so the
    caller can populate a KV cache during prefill."""
    a = cfg.attn
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(b, s, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(b, s, a.num_kv_heads, a.head_dim)
    sin, cos = rope_angles(positions, a.head_dim, a.rope_theta, a.m_rope_sections)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    o = multihead_attention(q, k, v, a, exec_cfg)
    return o.reshape(b, s, -1) @ p["wo"], (k, v)


def attention_extend_block(
    p: dict,
    x: jax.Array,             # (B, K, D_model) - K new tokens
    k_cache: jax.Array,       # (B, KV, S_max, D)
    v_cache: jax.Array,
    pos: jax.Array,           # (B,) first new position
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked decode: K new tokens attend over prefix + themselves.

    Used by speculative-decoding verification (target model scores K draft
    tokens in one pass) and by continuation after rollback."""
    a = cfg.attn
    b, kk, _ = x.shape
    q = (x @ p["wq"]).reshape(b, kk, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(b, kk, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(b, kk, a.num_kv_heads, a.head_dim)
    prope = pos[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
    if a.m_rope_sections is not None:
        prope = jnp.broadcast_to(prope, (3, b, kk))
    sin, cos = rope_angles(prope, a.head_dim, a.rope_theta, a.m_rope_sections)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)

    def write(cache, new, p0):
        return jax.lax.dynamic_update_slice(cache, new, (0, p0, 0))

    k_cache = jax.vmap(write)(k_cache, k.transpose(0, 2, 1, 3), pos)
    v_cache = jax.vmap(write)(v_cache, v.transpose(0, 2, 1, 3), pos)

    kvh, smax = k_cache.shape[1], k_cache.shape[2]
    g = a.num_heads // kvh
    qh = q.reshape(b, kk, kvh, g, a.head_dim)
    scores = jnp.einsum("bsqgd,bqtd->bqgst", qh, k_cache).astype(jnp.float32) * (
        a.head_dim ** -0.5
    )
    qpos = pos[:, None] + jnp.arange(kk)[None, :]                  # (B, K)
    mask = jnp.arange(smax)[None, None, :] <= qpos[:, :, None]     # (B, K, S)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bqgst,bqtd->bsqgd", probs, v_cache).reshape(b, kk, -1)
    return o @ p["wo"], k_cache, v_cache


def attention_decode_block(
    p: dict,
    x: jax.Array,             # (B, 1, D_model)
    k_cache: jax.Array,       # (B, KV, S_max, D)
    v_cache: jax.Array,
    pos: jax.Array,           # (B,) position to write at / attend through
    positions_rope: jax.Array,  # (B, 1) or (3, B, 1)
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step: write new k/v at `pos`, attend over prefix."""
    a = cfg.attn
    b = x.shape[0]
    q = (x @ p["wq"]).reshape(b, 1, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(b, 1, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(b, 1, a.num_kv_heads, a.head_dim)
    sin, cos = rope_angles(positions_rope, a.head_dim, a.rope_theta, a.m_rope_sections)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    # write new k/v at per-sequence position `pos` (scatter, not a full-cache
    # rewrite - decode is memory-bound, touching the whole cache twice would
    # double its HBM traffic).
    def write(cache, new, p):
        return jax.lax.dynamic_update_slice(cache, new, (0, p, 0))

    k_cache = jax.vmap(write)(k_cache, k.transpose(0, 2, 1, 3), pos)
    v_cache = jax.vmap(write)(v_cache, v.transpose(0, 2, 1, 3), pos)
    o = decode_attention(q, k_cache, v_cache, pos, exec_cfg)
    return o.reshape(b, 1, -1) @ p["wo"], k_cache, v_cache


def attention_paged_decode_block(
    p: dict,
    x: jax.Array,             # (B, 1, D_model)
    k_pages: jax.Array,       # (L, NBp, KV, bs, D) - the whole pool
    v_pages: jax.Array,
    layer: jax.Array,         # () int32 - this layer's index into the pool
    tables: jax.Array,        # (B, NB) int32 dump-padded block tables
    lengths: jax.Array,       # (B,) cached tokens (new token's position)
    positions_rope: jax.Array,  # (B, 1)
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
    max_len: int = 0,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gather-free decode step against PagedKVPool storage.

    The dense path (`attention_decode_block`) needs the engine to gather
    each sequence's pages into a contiguous (B, KV, S_max, D) cache first;
    this variant hands the pool's whole page arrays, the layer index and
    the block tables straight to `kops.paged_decode_attention`, and
    returns the step's own (k, v) for the caller to `scatter_append` into
    the pool. m-RoPE is unsupported (the engine gates VLM families to the
    gather path)."""
    a = cfg.attn
    assert a.m_rope_sections is None, "paged decode does not support m-rope"
    b = x.shape[0]
    q = (x @ p["wq"]).reshape(b, 1, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(b, 1, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(b, 1, a.num_kv_heads, a.head_dim)
    sin, cos = rope_angles(positions_rope, a.head_dim, a.rope_theta, None)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    from repro.kernels import ops as kops

    o = kops.paged_decode_attention(
        q, k_pages, v_pages, layer, tables, lengths, k, v, max_len=max_len,
        impl=impl)
    return o.reshape(b, 1, -1) @ p["wo"], k, v


def attention_paged_chunk_block(
    p: dict,
    x: jax.Array,             # (1, C, D_model) - one sequence's chunk
    k_pages: jax.Array,       # (NBp, KV, bs, D) - one pool layer
    v_pages: jax.Array,
    table: jax.Array,         # (NB,) int32 block table covering ctx0 tokens
    ctx0: int,                # static: cached tokens before this chunk
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
    impl: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused chunked prefill: C new tokens of one sequence attend over the
    sequence's paged cached context plus themselves (causal), without the
    engine re-running the backbone over the whole prefix. Returns
    (out, k, v) with k/v (1, C, KV, D) for `scatter_chunk`."""
    a = cfg.attn
    assert a.m_rope_sections is None, "paged prefill does not support m-rope"
    _, c, _ = x.shape
    q = (x @ p["wq"]).reshape(1, c, a.num_heads, a.head_dim)
    k = (x @ p["wk"]).reshape(1, c, a.num_kv_heads, a.head_dim)
    v = (x @ p["wv"]).reshape(1, c, a.num_kv_heads, a.head_dim)
    positions = ctx0 + jnp.arange(c, dtype=jnp.int32)[None, :]    # (1, C)
    sin, cos = rope_angles(positions, a.head_dim, a.rope_theta, None)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    from repro.kernels import ops as kops

    o = kops.paged_prefill_attention(q, k_pages, v_pages, table, ctx0, k, v,
                                     impl=impl)
    return o.reshape(1, c, -1) @ p["wo"], k, v
