"""Mamba2 (SSD) mixer for the Zamba2 hybrid (arXiv:2411.15242).

Selective state-space recurrence (per head, state N, head channels P):
    h_t = a_t h_{t-1} + dt_t * B_t x_t^T         h in R^{N x P},  a_t = exp(A dt_t)
    y_t = C_t^T h_t + D * x_t

Chunked SSD form mirrors rwkv6.py: intra-chunk work is batched einsums
(fully counted by XLA cost analysis); the inter-chunk state recurrence is a
small `lax.scan`. Scalar-per-head decays make the log-space factorization
exact; per-step log-decays are clamped to [-DECAY_CLAMP, 0] and intra-chunk
factors centered at half the chunk total, bounding exponents by
DECAY_CLAMP * chunk / 2 = 64 (fp32-safe).

B and C are shared across heads (n_groups=1), matching Zamba2.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import ExecConfig, DEFAULT_EXEC, rmsnorm

DECAY_CLAMP = 1.0


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.state_dim
    return d_inner, nheads, conv_ch


def init_mamba2(rng: jax.Array, cfg: ModelConfig) -> dict:
    """Per-segment projections (z / x / B / C / dt kept as separate weights
    so each shards cleanly on the tensor-model axis - a fused in_proj would
    put segment boundaries inside shards)."""
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nheads, _ = dims(cfg)
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 7)
    sc = d ** -0.5
    return {
        "w_z": (jax.random.normal(ks[0], (d, d_inner)) * sc).astype(dtype),
        "w_x": (jax.random.normal(ks[1], (d, d_inner)) * sc).astype(dtype),
        "w_b": (jax.random.normal(ks[2], (d, s.state_dim)) * sc).astype(dtype),
        "w_c": (jax.random.normal(ks[3], (d, s.state_dim)) * sc).astype(dtype),
        "w_dt": (jax.random.normal(ks[4], (d, nheads)) * sc).astype(dtype),
        "conv_x": (jax.random.normal(ks[5], (s.conv_width, d_inner)) * 0.5).astype(dtype),
        "conv_b": (jax.random.normal(ks[6], (s.conv_width, s.state_dim)) * 0.5).astype(dtype),
        "conv_c": (jax.random.normal(ks[6], (s.conv_width, s.state_dim)) * 0.5).astype(dtype),
        "conv_bias_x": jnp.zeros((d_inner,), dtype),
        "conv_bias_b": jnp.zeros((s.state_dim,), dtype),
        "conv_bias_c": jnp.zeros((s.state_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 8.0, nheads)).astype(jnp.float32),  # A = -exp(a_log)
        "dt_bias": jnp.full((nheads,), -2.0, jnp.float32),  # softplus(-2) ~ 0.13
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "norm": jnp.ones((d_inner,), jnp.float32),
        "out_proj": (jax.random.normal(ks[2], (d_inner, d)) * d_inner ** -0.5).astype(dtype),
    }


def causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array, prev: jax.Array | None = None):
    """Depthwise causal conv, width W. xbc: (B, T, C), w: (W, C).

    `prev` is the (B, W-1, C) tail of the previous segment (decode carry);
    returns (out, new_prev)."""
    width = w.shape[0]
    bsz, t, c = xbc.shape
    if prev is None:
        prev = jnp.zeros((bsz, width - 1, c), xbc.dtype)
    padded = jnp.concatenate([prev, xbc], axis=1)
    out = sum(padded[:, i : i + t] * w[i] for i in range(width)) + b
    return jax.nn.silu(out), padded[:, -(width - 1) :]


def ssd_chunked(
    xh: jax.Array,    # (B, T, H, P)
    b_in: jax.Array,  # (B, T, N)  shared across heads
    c_in: jax.Array,  # (B, T, N)
    dt: jax.Array,    # (B, T, H)  fp32, post-softplus
    a_log: jax.Array,  # (H,)
    state0: jax.Array | None = None,  # (B, H, N, P) fp32
    chunk: int = 128,
):
    """Chunked SSD scan. Returns (y (B,T,H,P) fp32, final_state)."""
    bsz, t, h, p = xh.shape
    n = b_in.shape[-1]
    if t % chunk:
        # pad to a chunk multiple: dt=0 kills both the state update and the
        # decay (la = -exp(a_log)*0 = 0), making the padding exact.
        pad = chunk - t % chunk
        p4 = [(0, 0), (0, pad), (0, 0), (0, 0)]
        p3 = [(0, 0), (0, pad), (0, 0)]
        y, state = ssd_chunked(
            jnp.pad(xh, p4), jnp.pad(b_in, p3), jnp.pad(c_in, p3),
            jnp.pad(dt, p3), a_log, state0, chunk)
        return y[:, :t], state
    nc = t // chunk
    # intra-chunk tensors stay in the activation dtype (bf16 in-model;
    # exponents are fp32-computed then cast - bf16 shares fp32's exponent
    # range so the centered factors cannot overflow). Only the cumulative
    # decays and the carried state stay fp32. Halves the per-layer backward
    # workspace (EXPERIMENTS.md §Perf iteration 6).
    cdt = xh.dtype
    la = jnp.clip(-jnp.exp(a_log) * dt, -DECAY_CLAMP, 0.0)  # (B,T,H) f32
    la = la.reshape(bsz, nc, chunk, h)
    dtc = dt.reshape(bsz, nc, chunk, h)
    xc = xh.reshape(bsz, nc, chunk, h, p)
    bc = b_in.reshape(bsz, nc, chunk, n)
    cc = c_in.reshape(bsz, nc, chunk, n)

    cum = jnp.cumsum(la, axis=2)                       # inclusive (B,nc,Lc,H)
    m = cum[:, :, -1]                                  # (B,nc,H)
    half = 0.5 * m[:, :, None]

    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i.B_j) x_j
    c_f = cc[..., None, :] * jnp.exp(cum - half)[..., None].astype(cdt)
    b_f = bc[..., None, :] * (jnp.exp(half - cum) * dtc)[..., None].astype(cdt)
    scores = jnp.einsum("bcihn,bcjhn->bchij", c_f, b_f)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))    # inclusive diagonal
    scores = jnp.where(mask[None, None, None], scores, jnp.zeros((), scores.dtype))
    y = jnp.einsum("bchij,bcjhp->bcihp", scores, xc,
                   preferred_element_type=jnp.float32)

    # inter-chunk state recurrence
    if state0 is None:
        state0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    c_st = cc[..., None, :] * jnp.exp(cum)[..., None].astype(cdt)  # from h0
    b_st = bc[..., None, :] * (jnp.exp(m[:, :, None] - cum) * dtc)[..., None].astype(cdt)

    def step(s, inp):
        c_c, b_c, x_c, m_c = inp
        y_state = jnp.einsum("blhn,bhnp->blhp", c_c.astype(jnp.float32), s)
        s = s * jnp.exp(m_c)[..., None, None] + jnp.einsum(
            "blhn,blhp->bhnp", b_c.astype(jnp.float32), x_c.astype(jnp.float32))
        return s, y_state

    xs = tuple(jnp.moveaxis(zz, 1, 0) for zz in (c_st, b_st, xc, m))
    state, y_state = jax.lax.scan(step, state0, xs)
    y = y + jnp.moveaxis(y_state, 0, 1)
    return y.reshape(bsz, t, h, p), state


def ssd_step(
    xh: jax.Array,    # (B, H, P)
    b_in: jax.Array,  # (B, N)
    c_in: jax.Array,  # (B, N)
    dt: jax.Array,    # (B, H) fp32
    a_log: jax.Array,
    state: jax.Array,  # (B, H, N, P) fp32
):
    la = jnp.clip(-jnp.exp(a_log) * dt, -DECAY_CLAMP, 0.0)
    xf = xh.astype(jnp.float32)
    upd = jnp.einsum("bn,bhp->bhnp", b_in.astype(jnp.float32), xf * dt[..., None])
    state = state * jnp.exp(la)[..., None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", c_in.astype(jnp.float32), state)
    return y, state


def mamba2_block(
    p: dict,
    x: jax.Array,                  # (B, T, D)
    cfg: ModelConfig,
    state0: jax.Array | None = None,
    conv_prev: jax.Array | None = None,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
):
    """Full-sequence Mamba2 block. Returns (out, (ssm_state, conv_state))."""
    s = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    bsz, t, _ = x.shape
    z = x @ p["w_z"]
    dt_raw = x @ p["w_dt"]
    if conv_prev is None:
        cp_x = cp_b = cp_c = None
    else:
        cp_x, cp_b, cp_c = jnp.split(conv_prev, [d_inner, d_inner + s.state_dim], axis=-1)
    xs, cs_x = causal_conv(x @ p["w_x"], p["conv_x"], p["conv_bias_x"], cp_x)
    b_in, cs_b = causal_conv(x @ p["w_b"], p["conv_b"], p["conv_bias_b"], cp_b)
    c_in, cs_c = causal_conv(x @ p["w_c"], p["conv_c"], p["conv_bias_c"], cp_c)
    conv_state = jnp.concatenate([cs_x, cs_b, cs_c], axis=-1)
    xh = xs.reshape(bsz, t, nheads, s.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    if exec_cfg.kernels:
        from repro.kernels import ops as kops

        y, state = kops.mamba2_ssd(xh, b_in, c_in, dt, p["a_log"], state0, chunk=s.chunk_size)
    else:
        y, state = ssd_chunked(xh, b_in, c_in, dt, p["a_log"], state0, chunk=s.chunk_size)
    y = y + p["d_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, t, d_inner)
    y = rmsnorm(p["norm"], y.astype(x.dtype), cfg.norm_eps) * jax.nn.silu(z)
    return y @ p["out_proj"], (state, conv_state)


def mamba2_step(
    p: dict,
    x: jax.Array,                  # (B, D)
    state: jax.Array,              # (B, H, N, P)
    conv_prev: jax.Array,          # (B, W-1, C)
    cfg: ModelConfig,
):
    s = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    bsz = x.shape[0]
    z = x @ p["w_z"]
    dt_raw = x @ p["w_dt"]
    cp_x, cp_b, cp_c = jnp.split(conv_prev, [d_inner, d_inner + s.state_dim], axis=-1)
    xs, cs_x = causal_conv((x @ p["w_x"])[:, None], p["conv_x"], p["conv_bias_x"], cp_x)
    b_in, cs_b = causal_conv((x @ p["w_b"])[:, None], p["conv_b"], p["conv_bias_b"], cp_b)
    c_in, cs_c = causal_conv((x @ p["w_c"])[:, None], p["conv_c"], p["conv_bias_c"], cp_c)
    conv_state = jnp.concatenate([cs_x, cs_b, cs_c], axis=-1)
    xs, b_in, c_in = xs[:, 0], b_in[:, 0], c_in[:, 0]
    xh = xs.reshape(bsz, nheads, s.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    y, state = ssd_step(xh, b_in, c_in, dt, p["a_log"], state)
    y = y + p["d_skip"][:, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, d_inner)
    y = rmsnorm(p["norm"], y.astype(x.dtype), cfg.norm_eps) * jax.nn.silu(z)
    return y @ p["out_proj"], (state, conv_state)
