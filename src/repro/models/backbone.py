"""Generic decoder backbone over the six architecture families.

Public contract (used by serving, training, dry-run, benchmarks):

    init_params(rng, cfg)                          -> params pytree
    forward(params, batch, cfg, exec_cfg)          -> logits (B, S, V)
    prefill(params, batch, cfg, exec_cfg)          -> (last_logits, cache)
    init_cache(cfg, batch, max_seq, dtype)         -> cache pytree
    serve_step(params, cache, tokens, cfg, ...)    -> (logits (B, V), cache)

`batch` is a dict: {"tokens": (B,S) int32} or, for stubbed modality
frontends, {"embeds": (B,S,D)}; vlm adds {"positions": (3,B,S)} (M-RoPE).

Layer stacks are `lax.scan` over stacked parameters (HLO size independent
of depth); `exec_cfg.static_unroll` switches to Python loops for the cost
dry-run (XLA cost analysis counts scan bodies once - see DESIGN.md §7).
Training remat: the scan body is `jax.checkpoint`-ed, so only layer-boundary
activations are saved.

`init_params` and the step functions the serving engine calls (`prefill`,
`serve_step`, `serve_step_paged`, `prefill_chunk_paged`) are jitted with
the configs static, so a shape seen once in a process is not compiled
again.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import mamba2, rwkv6
from repro.models.attention import (
    attention_block,
    attention_decode_block,
    attention_paged_chunk_block,
    attention_paged_decode_block,
    init_attention,
)
from repro.models.config import ModelConfig
from repro.models.layers import (
    DEFAULT_EXEC,
    ExecConfig,
    constrain_carry,
    embed_tokens,
    init_embed,
    init_moe,
    init_rmsnorm,
    init_swiglu,
    lm_logits,
    moe_ffn,
    rmsnorm,
    swiglu,
)

Params = dict
Cache = dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(rng: jax.Array, cfg: ModelConfig) -> dict:
    """One layer's params; the caller stacks these along a leading L axis."""
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(rng, 3)
    p: dict = {"norm1": init_rmsnorm(d), "norm2": init_rmsnorm(d)}
    if cfg.family in ("dense", "audio", "vlm"):
        p["attn"] = init_attention(k1, cfg)
        p["ffn"] = init_swiglu(k2, d, cfg.d_ff, dtype)
    elif cfg.family == "moe":
        p["attn"] = init_attention(k1, cfg)
        p["moe"] = init_moe(k2, cfg)
    elif cfg.family == "ssm":
        p["time_mix"] = rwkv6.init_time_mix(k1, cfg)
        p["channel_mix"] = rwkv6.init_channel_mix(k2, cfg)
    elif cfg.family == "hybrid":
        p["mamba"] = mamba2.init_mamba2(k1, cfg)
        p["ffn"] = init_swiglu(k2, d, cfg.d_ff, dtype)
    else:
        raise ValueError(cfg.family)
    return p


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    k_embed, k_layers, k_shared = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    # stacked init: vmap one-layer init over L keys
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    params: Params = {"tok": init_embed(k_embed, cfg), "layers": layers,
                      "final_norm": init_rmsnorm(cfg.d_model)}
    if cfg.family == "hybrid":
        params["shared_attn"] = {
            "attn": init_attention(k_shared, cfg),
            "norm": init_rmsnorm(cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> Cache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    c: Cache = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        a = cfg.attn
        c["k"] = jnp.zeros((cfg.num_layers, batch, a.num_kv_heads, max_seq, a.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
    elif cfg.family == "ssm":
        r = cfg.rwkv
        h = cfg.d_model // r.head_dim
        c["state"] = jnp.zeros((cfg.num_layers, batch, h, r.head_dim, r.head_dim), jnp.float32)
        c["x_prev_att"] = jnp.zeros((cfg.num_layers, batch, cfg.d_model), dtype)
        c["x_prev_ffn"] = jnp.zeros((cfg.num_layers, batch, cfg.d_model), dtype)
    elif cfg.family == "hybrid":
        s = cfg.ssm
        d_inner, nheads, conv_ch = mamba2.dims(cfg)
        taps = cfg.num_layers // cfg.hybrid_attn_every
        a = cfg.attn
        c["ssm_state"] = jnp.zeros((cfg.num_layers, batch, nheads, s.state_dim, s.head_dim), jnp.float32)
        c["conv_state"] = jnp.zeros((cfg.num_layers, batch, s.conv_width - 1, conv_ch), dtype)
        c["k"] = jnp.zeros((taps, batch, a.num_kv_heads, max_seq, a.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
    return c


# ---------------------------------------------------------------------------
# full-sequence layer applications (train / prefill)
# ---------------------------------------------------------------------------
def _attn_layer_full(lp, x, positions, cfg, exec_cfg):
    h, kv = attention_block(lp["attn"], rmsnorm(lp["norm1"], x, cfg.norm_eps), positions, cfg, exec_cfg)
    x = x + h
    xn = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        x = x + moe_ffn(lp["moe"], xn, cfg, exec_cfg)
    else:
        x = x + swiglu(lp["ffn"], xn)
    return constrain_carry(x, exec_cfg), kv


def _rwkv_layer_full(lp, x, cfg, exec_cfg, x_prev_att=None, x_prev_ffn=None, state0=None):
    b = x.shape[0]
    zp = jnp.zeros((b, cfg.d_model), x.dtype)
    h, (last_att, state) = rwkv6.time_mix(
        lp["time_mix"], rmsnorm(lp["norm1"], x, cfg.norm_eps),
        zp if x_prev_att is None else x_prev_att, state0, cfg, exec_cfg)
    x = x + h
    h, last_ffn = rwkv6.channel_mix(
        lp["channel_mix"], rmsnorm(lp["norm2"], x, cfg.norm_eps),
        zp if x_prev_ffn is None else x_prev_ffn)
    return constrain_carry(x + h, exec_cfg), (last_att, last_ffn, state)


def _mamba_layer_full(lp, x, cfg, exec_cfg):
    h, (state, conv) = mamba2.mamba2_block(lp["mamba"], rmsnorm(lp["norm1"], x, cfg.norm_eps), cfg, exec_cfg=exec_cfg)
    x = x + h
    x = x + swiglu(lp["ffn"], rmsnorm(lp["norm2"], x, cfg.norm_eps))
    return constrain_carry(x, exec_cfg), (state, conv)


def _shared_attn_full(sp, x, positions, cfg, exec_cfg):
    h, kv = attention_block(sp["attn"], rmsnorm(sp["norm"], x, cfg.norm_eps), positions, cfg, exec_cfg)
    return x + h, kv


def _stack(cfg: ModelConfig, params: Params, x: jax.Array, positions, exec_cfg: ExecConfig,
           collect_cache: bool):
    """Run all layers over a full sequence. Returns (x, cache_pieces)."""
    layers = params["layers"]
    L = cfg.num_layers

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        if exec_cfg.static_unroll:
            kvs = []
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], layers)
                x, kv = _attn_layer_full(lp, x, positions, cfg, exec_cfg)
                if collect_cache:
                    kvs.append(kv)
            return x, (_stack_kv(kvs) if collect_cache else None)

        def body(xc, lp):
            xc, kv = _attn_layer_full(lp, xc, positions, cfg, exec_cfg)
            return xc, kv if collect_cache else None

        if exec_cfg.remat:
            body = jax.checkpoint(body)
        x, kvs = jax.lax.scan(body, x, layers)
        if collect_cache:
            k, v = kvs  # (L, B, S, KV, hd)
            return x, (k.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 2, 4))
        return x, None

    if cfg.family == "ssm":
        if exec_cfg.static_unroll:
            pieces = []
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], layers)
                x, pc = _rwkv_layer_full(lp, x, cfg, exec_cfg)
                if collect_cache:
                    pieces.append(pc)
            if collect_cache:
                la, lf, st = zip(*pieces)
                return x, (jnp.stack(la), jnp.stack(lf), jnp.stack(st))
            return x, None

        def body(xc, lp):
            xc, pc = _rwkv_layer_full(lp, xc, cfg, exec_cfg)
            return xc, pc if collect_cache else None

        if exec_cfg.remat:
            body = jax.checkpoint(body)
        x, pieces = jax.lax.scan(body, x, layers)
        return x, pieces

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        taps = L // every
        sp = params["shared_attn"]
        grouped = jax.tree.map(lambda a: a.reshape(taps, every, *a.shape[1:]), layers)

        inner = _mamba_layer_full

        def tap_body(xc, glp):
            states, convs = [], []
            for j in range(every):  # small static inner loop
                lp = jax.tree.map(lambda a: a[j], glp)
                xc, (st, cv) = inner(lp, xc, cfg, exec_cfg)
                states.append(st)
                convs.append(cv)
            xc, kv = _shared_attn_full(sp, xc, positions, cfg, exec_cfg)
            return xc, (jnp.stack(states), jnp.stack(convs), kv) if collect_cache else None

        if exec_cfg.static_unroll:
            pieces = []
            for i in range(taps):
                glp = jax.tree.map(lambda a: a[i], grouped)
                x, pc = tap_body(x, glp)
                if collect_cache:
                    pieces.append(pc)
            if collect_cache:
                sts, cvs, kvs = zip(*pieces)
                k, v = _stack_kv(kvs)
                return x, (jnp.concatenate(sts), jnp.concatenate(cvs), (k, v))
            return x, None

        body = tap_body
        if exec_cfg.remat:
            body = jax.checkpoint(body)
        x, pieces = jax.lax.scan(body, x, grouped)
        if collect_cache:
            sts, cvs, (k, v) = pieces  # sts: (taps, every, B, ...)
            sts = sts.reshape(L, *sts.shape[2:])
            cvs = cvs.reshape(L, *cvs.shape[2:])
            return x, (sts, cvs, (k.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 2, 4)))
        return x, None

    raise ValueError(cfg.family)


def _stack_kv(kvs):
    k = jnp.stack([kv[0] for kv in kvs])  # (L, B, S, KV, hd)
    v = jnp.stack([kv[1] for kv in kvs])
    return k.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 2, 4)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def _embed_in(params, batch: dict, cfg: ModelConfig):
    if "embeds" in batch:
        return batch["embeds"]
    return embed_tokens(params["tok"], batch["tokens"])


def _positions_in(batch: dict, b: int, s: int, cfg: ModelConfig):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.attn is not None and cfg.attn.m_rope_sections is not None:
        pos = jnp.broadcast_to(pos, (3, b, s))
    return pos


def forward(params: Params, batch: dict, cfg: ModelConfig,
            exec_cfg: ExecConfig = DEFAULT_EXEC) -> jax.Array:
    """Training forward: logits for every position."""
    x = _embed_in(params, batch, cfg)
    b, s, _ = x.shape
    positions = _positions_in(batch, b, s, cfg)
    x, _ = _stack(cfg, params, x, positions, exec_cfg, collect_cache=False)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["tok"], x, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "exec_cfg"))
def prefill(params: Params, batch: dict, cfg: ModelConfig,
            exec_cfg: ExecConfig = DEFAULT_EXEC) -> tuple[jax.Array, Cache]:
    """Prompt processing: returns (logits at last position (B, V), cache)."""
    x = _embed_in(params, batch, cfg)
    b, s, _ = x.shape
    positions = _positions_in(batch, b, s, cfg)
    x, pieces = _stack(cfg, params, x, positions, exec_cfg, collect_cache=True)
    x = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = lm_logits(params["tok"], x, cfg)[:, 0]
    pos = jnp.full((b,), s, jnp.int32)
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        k, v = pieces
        cache = {"k": k, "v": v, "pos": pos}
    elif cfg.family == "ssm":
        la, lf, st = pieces
        cache = {"state": st, "x_prev_att": la, "x_prev_ffn": lf, "pos": pos}
    else:  # hybrid
        sts, cvs, (k, v) = pieces
        cache = {"ssm_state": sts, "conv_state": cvs, "k": k, "v": v, "pos": pos}
    return logits, cache


def _grow_cache(cache: Cache, cfg: ModelConfig, max_seq: int) -> Cache:
    """Pad prefill KV out to `max_seq` slots for decoding."""
    if "k" not in cache:
        return cache
    cur = cache["k"].shape[3]
    if cur >= max_seq:
        return cache
    pad = [(0, 0)] * 5
    pad[3] = (0, max_seq - cur)
    out = dict(cache)
    out["k"] = jnp.pad(cache["k"], pad)
    out["v"] = jnp.pad(cache["v"], pad)
    return out


# --- decode-path layer steps ---
def _attn_layer_step(lp, x, kc, vc, pos, prope, cfg, exec_cfg):
    h, kc, vc = attention_decode_block(
        lp["attn"], rmsnorm(lp["norm1"], x, cfg.norm_eps), kc, vc, pos, prope, cfg, exec_cfg)
    x = x + h
    xn = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        x = x + moe_ffn(lp["moe"], xn, cfg, exec_cfg)
    else:
        x = x + swiglu(lp["ffn"], xn)
    return x, kc, vc


@functools.partial(jax.jit, static_argnames=("cfg", "exec_cfg"))
def serve_step(params: Params, cache: Cache, tokens: jax.Array, cfg: ModelConfig,
               exec_cfg: ExecConfig = DEFAULT_EXEC,
               embeds: Optional[jax.Array] = None) -> tuple[jax.Array, Cache]:
    """One decode step for a batch of sequences.

    tokens: (B,) int32 (ignored if `embeds` (B, D) given - audio frontend).
    Cache position advances by 1. Returns (logits (B, V), new cache).
    """
    pos = cache["pos"]
    b = pos.shape[0]
    x = embeds if embeds is not None else embed_tokens(params["tok"], tokens)  # (B, D)
    x = x[:, None, :]                                                          # (B, 1, D)
    prope = pos[:, None].astype(jnp.int32)  # (B, 1)
    if cfg.attn is not None and cfg.attn.m_rope_sections is not None:
        prope = jnp.broadcast_to(prope, (3, b, 1))
    L = cfg.num_layers
    layers = params["layers"]

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        if exec_cfg.static_unroll:
            ks, vs = [], []
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], layers)
                x, kc, vc = _attn_layer_step(lp, x, cache["k"][i], cache["v"][i], pos, prope, cfg, exec_cfg)
                ks.append(kc)
                vs.append(vc)
            newc = {"k": jnp.stack(ks), "v": jnp.stack(vs), "pos": pos + 1}
        else:
            def body(xc, inp):
                lp, kc, vc = inp
                xc, kc, vc = _attn_layer_step(lp, xc, kc, vc, pos, prope, cfg, exec_cfg)
                return xc, (kc, vc)

            x, (k, v) = jax.lax.scan(body, x, (layers, cache["k"], cache["v"]))
            newc = {"k": k, "v": v, "pos": pos + 1}

    elif cfg.family == "ssm":
        xt = x[:, 0]

        def body(xc, inp):
            lp, st, xa, xf = inp
            h, last_a, st = rwkv6.time_mix_step(lp["time_mix"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), xa, st, cfg)
            xc = xc + h
            h, last_f = rwkv6.channel_mix_step(lp["channel_mix"], rmsnorm(lp["norm2"], xc, cfg.norm_eps), xf)
            return xc + h, (st, last_a, last_f)

        if exec_cfg.static_unroll:
            sts, las, lfs = [], [], []
            for i in range(L):
                lp = jax.tree.map(lambda a: a[i], layers)
                xt, (st, la, lf) = body(xt, (lp, cache["state"][i], cache["x_prev_att"][i], cache["x_prev_ffn"][i]))
                sts.append(st); las.append(la); lfs.append(lf)
            newc = {"state": jnp.stack(sts), "x_prev_att": jnp.stack(las),
                    "x_prev_ffn": jnp.stack(lfs), "pos": pos + 1}
        else:
            xt, (st, la, lf) = jax.lax.scan(
                body, xt, (layers, cache["state"], cache["x_prev_att"], cache["x_prev_ffn"]))
            newc = {"state": st, "x_prev_att": la, "x_prev_ffn": lf, "pos": pos + 1}
        x = xt[:, None]

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        taps = L // every
        sp = params["shared_attn"]
        grouped = jax.tree.map(lambda a: a.reshape(taps, every, *a.shape[1:]), layers)
        xt = x[:, 0]

        def tap_body(xc, inp):
            glp, sts, cvs, kc, vc = inp
            new_sts, new_cvs = [], []
            for j in range(every):
                lp = jax.tree.map(lambda a: a[j], glp)
                h, (st, cv) = mamba2.mamba2_step(
                    lp["mamba"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), sts[j], cvs[j], cfg)
                xc = xc + h
                xc = xc + swiglu(lp["ffn"], rmsnorm(lp["norm2"], xc, cfg.norm_eps))
                new_sts.append(st); new_cvs.append(cv)
            h, kc, vc = attention_decode_block(
                sp["attn"], rmsnorm(sp["norm"], xc[:, None], cfg.norm_eps), kc, vc, pos, prope, cfg, exec_cfg)
            xc = xc + h[:, 0]
            return xc, (jnp.stack(new_sts), jnp.stack(new_cvs), kc, vc)

        ssm_g = cache["ssm_state"].reshape(taps, every, *cache["ssm_state"].shape[1:])
        cv_g = cache["conv_state"].reshape(taps, every, *cache["conv_state"].shape[1:])
        if exec_cfg.static_unroll:
            pieces = []
            for i in range(taps):
                glp = jax.tree.map(lambda a: a[i], grouped)
                xt, pc = tap_body(xt, (glp, ssm_g[i], cv_g[i], cache["k"][i], cache["v"][i]))
                pieces.append(pc)
            sts, cvs, ks, vs = (jnp.stack([p[i] for p in pieces]) for i in range(4))
        else:
            xt, (sts, cvs, ks, vs) = jax.lax.scan(tap_body, xt, (grouped, ssm_g, cv_g, cache["k"], cache["v"]))
        newc = {
            "ssm_state": sts.reshape(L, *sts.shape[2:]),
            "conv_state": cvs.reshape(L, *cvs.shape[2:]),
            "k": ks, "v": vs, "pos": pos + 1,
        }
        x = xt[:, None]
    else:
        raise ValueError(cfg.family)

    xn = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["tok"], xn, cfg)[:, 0]
    return logits, newc


def serve_step_paged(params: Params, pages_k: jax.Array, pages_v: jax.Array,
                     tables: jax.Array, lengths: jax.Array, tokens: jax.Array,
                     cfg: ModelConfig, exec_cfg: ExecConfig = DEFAULT_EXEC,
                     max_len: int = 0, impl: str = "auto",
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One gather-free decode step straight off PagedKVPool storage.

    pages_k/pages_v: (L, NBp, KV, bs, D) - the pool's full page arrays;
    tables: (B, NB) int32 dump-padded block tables; lengths: (B,) cached
    tokens per sequence; tokens: (B,) int32; max_len: static batch-max
    length including the new token. Returns (logits (B, V),
    k_tok (L, B, KV, D), v_tok) - the step's own K/V for `scatter_append`.

    The layer body is operation-for-operation `_attn_layer_step`, with the
    gather-then-update cache replaced by the paged attention op; on CPU
    (impl="jnp") the logits are bit-identical to `serve_step` over the
    gathered cache. Dense + MoE families only (decode feeds all B tokens
    through MoE as one group either way, so MoE capacity routing is
    unaffected; recurrent/vlm families keep the gather path).

    `max_len` only sizes the jnp twin's densified cache; the Pallas kernel
    reads `lengths` at run time, so on that path it is dropped and the
    step compiles once per (batch, table width), not once per step."""
    assert cfg.family in ("dense", "moe"), cfg.family
    impl = kops.resolve_impl(impl)
    return _serve_step_paged(params, pages_k, pages_v, tables, lengths, tokens,
                             cfg, exec_cfg, max_len if impl == "jnp" else 0,
                             impl)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "exec_cfg", "max_len", "impl"))
def _serve_step_paged(params, pages_k, pages_v, tables, lengths, tokens,
                      cfg, exec_cfg, max_len, impl):
    x = embed_tokens(params["tok"], tokens)[:, None, :]            # (B, 1, D)
    prope = lengths[:, None].astype(jnp.int32)                     # (B, 1)

    def body(xc, inp):
        lp, kp, vp = inp
        h, kt, vt = attention_paged_decode_block(
            lp["attn"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), kp, vp,
            tables, lengths, prope, cfg, exec_cfg, max_len=max_len, impl=impl)
        xc = xc + h
        xn = rmsnorm(lp["norm2"], xc, cfg.norm_eps)
        if cfg.family == "moe":
            xc = xc + moe_ffn(lp["moe"], xn, cfg, exec_cfg)
        else:
            xc = xc + swiglu(lp["ffn"], xn)
        return xc, (kt, vt)

    x, (kt, vt) = jax.lax.scan(body, x, (params["layers"], pages_k, pages_v))
    xn = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params["tok"], xn, cfg)[:, 0]
    return logits, kt[:, :, 0], vt[:, :, 0]                        # (L, B, KV, D)


def prefill_chunk_paged(params: Params, pages_k: jax.Array, pages_v: jax.Array,
                        table: jax.Array, ctx0: int, tokens: jax.Array,
                        cfg: ModelConfig, exec_cfg: ExecConfig = DEFAULT_EXEC,
                        impl: str = "auto",
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Incremental chunked prefill of ONE sequence against its paged context.

    Processes `tokens` (C,) at positions [ctx0, ctx0 + C) attending over the
    sequence's ctx0 cached tokens (via `table` (NB,)) plus itself causally -
    the whole-prefix recompute the engine's dense `_chunk_prefill` does is
    skipped. Returns (last_logits (1, V), k_c (L, KV, C, D), v_c) for
    `scatter_chunk`.

    Dense family only: MoE capacity routing drops tokens per *group*, so an
    MoE chunk processed alone routes differently than inside the full
    prefix - incremental results would diverge from the recompute path.

    `ctx0` is static only for the jnp twin, which slices the context by
    it; the Pallas kernel reads it at run time, so there it is traced and
    chunks of one length and table width share a compile."""
    assert cfg.family == "dense", cfg.family
    impl = kops.resolve_impl(impl)
    if impl == "jnp":
        return _prefill_chunk_static_ctx(params, pages_k, pages_v, table, ctx0,
                                         tokens, cfg, exec_cfg, impl)
    return _prefill_chunk_traced_ctx(params, pages_k, pages_v, table,
                                     jnp.int32(ctx0), tokens, cfg, exec_cfg,
                                     impl)


def _prefill_chunk_paged(params, pages_k, pages_v, table, ctx0, tokens, cfg,
                         exec_cfg, impl):
    c = tokens.shape[0]
    x = embed_tokens(params["tok"], tokens[None, :])               # (1, C, D)

    def body(xc, inp):
        lp, kp, vp = inp
        h, kt, vt = attention_paged_chunk_block(
            lp["attn"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), kp, vp,
            table, ctx0, cfg, exec_cfg, impl=impl)
        xc = xc + h
        xc = xc + swiglu(lp["ffn"], rmsnorm(lp["norm2"], xc, cfg.norm_eps))
        return xc, (kt, vt)

    x, (kt, vt) = jax.lax.scan(body, x, (params["layers"], pages_k, pages_v))
    xn = rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = lm_logits(params["tok"], xn, cfg)[:, 0]
    # kt: (L, 1, C, KV, D) -> (L, KV, C, D) for scatter_chunk
    return logits, kt[:, 0].transpose(0, 2, 1, 3), vt[:, 0].transpose(0, 2, 1, 3)


_prefill_chunk_static_ctx = jax.jit(
    _prefill_chunk_paged, static_argnames=("ctx0", "cfg", "exec_cfg", "impl"))
_prefill_chunk_traced_ctx = jax.jit(
    _prefill_chunk_paged, static_argnames=("cfg", "exec_cfg", "impl"))


def extend_step(params: Params, cache: Cache, tokens: jax.Array, cfg: ModelConfig,
                exec_cfg: ExecConfig = DEFAULT_EXEC) -> tuple[jax.Array, Cache]:
    """Process K new tokens against an existing cache (chunked decode).

    Used by speculative decoding: the target model verifies K draft tokens
    in one pass. tokens: (B, K) int32 -> (logits (B, K, V), new cache).
    Attention families extend the KV cache in place; recurrent families
    (ssm/hybrid) advance their state through the K tokens (the documented
    K-step chunked scan - DESIGN.md §4)."""
    from repro.models.attention import attention_extend_block

    pos = cache["pos"]
    b, kk = tokens.shape
    x = embed_tokens(params["tok"], tokens)
    layers = params["layers"]
    L = cfg.num_layers

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        def body(xc, inp):
            lp, kc, vc = inp
            h, kc, vc = attention_extend_block(
                lp["attn"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), kc, vc, pos, cfg, exec_cfg)
            xc = xc + h
            xn = rmsnorm(lp["norm2"], xc, cfg.norm_eps)
            if cfg.family == "moe":
                xc = xc + moe_ffn(lp["moe"], xn, cfg, exec_cfg)
            else:
                xc = xc + swiglu(lp["ffn"], xn)
            return xc, (kc, vc)

        x, (k, v) = jax.lax.scan(body, x, (layers, cache["k"], cache["v"]))
        newc = {"k": k, "v": v, "pos": pos + kk}

    elif cfg.family == "ssm":
        def body(xc, inp):
            lp, st, xa, xf = inp
            xc, (la, lf, st) = _rwkv_layer_full(lp, xc, cfg, exec_cfg, xa, xf, st)
            return xc, (st, la, lf)

        x, (st, la, lf) = jax.lax.scan(
            body, x, (layers, cache["state"], cache["x_prev_att"], cache["x_prev_ffn"]))
        newc = {"state": st, "x_prev_att": la, "x_prev_ffn": lf, "pos": pos + kk}

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        taps = L // every
        sp = params["shared_attn"]
        grouped = jax.tree.map(lambda a: a.reshape(taps, every, *a.shape[1:]), layers)
        ssm_g = cache["ssm_state"].reshape(taps, every, *cache["ssm_state"].shape[1:])
        cv_g = cache["conv_state"].reshape(taps, every, *cache["conv_state"].shape[1:])

        def tap_body(xc, inp):
            glp, sts, cvs, kc, vc = inp
            new_sts, new_cvs = [], []
            for j in range(every):
                lp = jax.tree.map(lambda a: a[j], glp)
                h, (st, cv) = mamba2.mamba2_block(
                    lp["mamba"], rmsnorm(lp["norm1"], xc, cfg.norm_eps), cfg,
                    state0=sts[j], conv_prev=cvs[j], exec_cfg=exec_cfg)
                xc = xc + h
                xc = xc + swiglu(lp["ffn"], rmsnorm(lp["norm2"], xc, cfg.norm_eps))
                new_sts.append(st); new_cvs.append(cv)
            h, kc, vc = attention_extend_block(
                sp["attn"], rmsnorm(sp["norm"], xc, cfg.norm_eps), kc, vc, pos, cfg, exec_cfg)
            return xc + h, (jnp.stack(new_sts), jnp.stack(new_cvs), kc, vc)

        x, (sts, cvs, ks, vs) = jax.lax.scan(tap_body, x, (grouped, ssm_g, cv_g, cache["k"], cache["v"]))
        newc = {
            "ssm_state": sts.reshape(L, *sts.shape[2:]),
            "conv_state": cvs.reshape(L, *cvs.shape[2:]),
            "k": ks, "v": vs, "pos": pos + kk,
        }
    else:
        raise ValueError(cfg.family)

    xn = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return lm_logits(params["tok"], xn, cfg), newc


def loss_fn(params: Params, batch: dict, cfg: ModelConfig,
            exec_cfg: ExecConfig = DEFAULT_EXEC) -> jax.Array:
    """Mean next-token cross-entropy (labels provided in batch).

    The gold logit is extracted with a one-hot masked reduction rather than
    take_along_axis: a gather over the vocab dim (sharded on "model") would
    force XLA to all-gather the full fp32 logits per device (~40 GiB/device
    at train_4k scale - EXPERIMENTS.md §Perf iteration 1); the masked sum
    partitions cleanly (local partial sum + psum)."""
    logits = forward(params, batch, cfg, exec_cfg)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = labels[..., None] == jax.lax.broadcasted_iota(jnp.int32, (1, 1, cfg.vocab_size), 2)
    gold = jnp.sum(jnp.where(onehot, lf, 0.0), axis=-1)
    ce = jnp.mean(lse - gold)
    if cfg.family == "moe":
        from repro.models.layers import moe_aux_loss

        x = _embed_in(params, batch, cfg)
        aux = moe_aux_loss(jax.tree.map(lambda a: a[0], params["layers"])["moe"], x, cfg)
        ce = ce + 0.01 * aux
    return ce
