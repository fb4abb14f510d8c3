"""RWKV6 "Finch" (arXiv:2404.05892): attention-free time-mix with
data-dependent per-channel decay, plus channel-mix FFN.

Recurrence (per head, head_dim = n):
    S_t = diag(w_t) S_{t-1} + k_t v_t^T            S in R^{n x n}
    y_t = r_t^T (diag(u) k_t v_t^T + S_{t-1})

Training/prefill uses a *chunked* parallel form (flash-linear-attention
style): all O(T * d^2) projection work and the O(T * Lc * d) intra-chunk
work are batched einsums (fully visible to XLA cost analysis); only the
O(T/Lc) inter-chunk state recurrence is a `lax.scan`, whose per-step
einsums are <1% of layer FLOPs (documented in DESIGN.md / roofline notes).

Numerical strategy: per-channel log-decays are clamped to
[-DECAY_CLAMP, -1e-4] and intra-chunk decay factors are centered at half
the chunk's total log-decay, bounding every exponent by
DECAY_CLAMP * chunk / 2 (fp32-safe for the default chunk of 16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import ExecConfig, DEFAULT_EXEC, rmsnorm

DECAY_CLAMP = 8.0
CHUNK = 16  # fp32-safe with DECAY_CLAMP (exponents <= 8 * 16 / 2 = 64)

_MIX_NAMES = ("w", "k", "v", "r", "g")


def init_time_mix(rng: jax.Array, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    r = cfg.rwkv
    h = d // r.head_dim
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 12)
    s = d ** -0.5
    return {
        "mu_x": jnp.zeros((d,), jnp.float32) + 0.5,
        "mus": jnp.full((5, d), 0.5, jnp.float32),
        "lora_mix_a": (jax.random.normal(ks[0], (d, 5, r.lora_dim_mix)) * s).astype(jnp.float32),
        "lora_mix_b": (jax.random.normal(ks[1], (5, r.lora_dim_mix, d)) * 0.01).astype(jnp.float32),
        "w0": jnp.full((d,), 0.5, jnp.float32),  # exp(0.5) ~ 1.65 decay rate
        "lora_w_a": (jax.random.normal(ks[2], (d, r.lora_dim_decay)) * s).astype(jnp.float32),
        "lora_w_b": (jax.random.normal(ks[3], (r.lora_dim_decay, d)) * 0.01).astype(jnp.float32),
        "wr": (jax.random.normal(ks[4], (d, d)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[5], (d, d)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[6], (d, d)) * s).astype(dtype),
        "wg": (jax.random.normal(ks[7], (d, d)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[8], (d, d)) * s).astype(dtype),
        "u": (jax.random.normal(ks[9], (h, r.head_dim)) * 0.1).astype(jnp.float32),
        "ln_x": jnp.ones((d,), jnp.float32),
    }


def init_channel_mix(rng: jax.Array, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "wk": (jax.random.normal(k1, (d, f)) * d ** -0.5).astype(dtype),
        "wv": (jax.random.normal(k2, (f, d)) * f ** -0.5).astype(dtype),
        "wr": (jax.random.normal(k3, (d, d)) * d ** -0.5).astype(dtype),
    }


def _ddlerp(p: dict, x: jax.Array, x_prev: jax.Array):
    """Data-dependent token-shift interpolation -> the 5 mixed inputs."""
    dt = x.dtype
    xx = (x_prev - x).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    base = xf + xx * p["mu_x"]
    lora = jnp.einsum("...d,dkl->...kl", base, p["lora_mix_a"])
    lora = jnp.tanh(lora)
    dyn = jnp.einsum("...kl,kld->...kd", lora, p["lora_mix_b"])  # (..., 5, d)
    mixed = xf[..., None, :] + xx[..., None, :] * (p["mus"] + dyn)
    return [mixed[..., i, :].astype(dt) for i in range(5)]


def _decay(p: dict, xw: jax.Array) -> jax.Array:
    """Per-channel log-decay log(w_t) in [-DECAY_CLAMP, -1e-4], fp32."""
    lw = jnp.tanh(xw.astype(jnp.float32) @ p["lora_w_a"]) @ p["lora_w_b"]
    rate = jnp.exp(jnp.clip(p["w0"] + lw, -6.0, jnp.log(DECAY_CLAMP)))
    return -jnp.clip(rate, 1e-4, DECAY_CLAMP)


def wkv_chunked(
    r: jax.Array,  # (B, T, H, N)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (B, T, H, N) fp32, negative
    u: jax.Array,  # (H, N)
    state0: jax.Array | None = None,  # (B, H, N, N) fp32
    chunk: int = CHUNK,
):
    """Chunked WKV6. Returns (y (B,T,H,N) fp32, final_state)."""
    b, t, h, n = r.shape
    if t % chunk:
        # pad to a chunk multiple: k=0 (no state contribution), logw=0 (no
        # decay) makes the padding exact; padded outputs are sliced away.
        pad = chunk - t % chunk
        pz = [(0, 0), (0, pad), (0, 0), (0, 0)]
        y, state = wkv_chunked(
            jnp.pad(r, pz), jnp.pad(k, pz), jnp.pad(v, pz), jnp.pad(logw, pz),
            u, state0, chunk)
        return y[:, :t], state
    nc = t // chunk
    rf, kf, vf = (a.astype(jnp.float32).reshape(b, nc, chunk, h, n) for a in (r, k, v))
    lw = logw.reshape(b, nc, chunk, h, n)

    cum = jnp.cumsum(lw, axis=2)                      # inclusive, (B,nc,Lc,H,N)
    cum_ex = cum - lw                                  # exclusive
    m = cum[:, :, -1]                                  # (B,nc,H,N) chunk total
    half = 0.5 * m[:, :, None]

    # intra-chunk: scores_ij = sum_d r_i k_j exp(cum_ex_i - cum_j), j < i
    a_in = rf * jnp.exp(cum_ex - half)                 # exponents <= |m|/2
    b_in = kf * jnp.exp(half - cum)
    scores = jnp.einsum("bcihn,bcjhn->bchij", a_in, b_in)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    scores = jnp.where(mask[None, None, None], scores, 0.0)
    y = jnp.einsum("bchij,bcjhn->bcihn", scores, vf)
    # diagonal (current-token) bonus term: (r_i . u k_i) v_i
    diag = jnp.einsum("bcihn,bcihn->bcih", rf, u * kf)
    y = y + diag[..., None] * vf

    # inter-chunk recurrence (state carry); per-step einsums are tiny
    if state0 is None:
        state0 = jnp.zeros((b, h, n, n), jnp.float32)
    a_st = rf * jnp.exp(cum_ex)                        # for y_state = a @ S
    k_st = kf * jnp.exp(m[:, :, None] - cum)           # decayed to chunk end

    def step(S, inp):
        a_c, k_c, v_c, m_c = inp                       # (B,Lc,H,N)...(B,H,N)
        y_state = jnp.einsum("blhn,bhnm->blhm", a_c, S)
        S = S * jnp.exp(m_c)[..., None] + jnp.einsum("blhn,blhm->bhnm", k_c, v_c)
        return S, y_state

    xs = tuple(jnp.moveaxis(z, 1, 0) for z in (a_st, k_st, vf, m))
    state, y_state = jax.lax.scan(step, state0, xs)
    y = y + jnp.moveaxis(y_state, 0, 1)
    return y.reshape(b, t, h, n), state


def wkv_step(
    r: jax.Array,  # (B, H, N)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (B, H, N) fp32
    u: jax.Array,  # (H, N)
    state: jax.Array,  # (B, H, N, N) fp32 (indexed [key_dim, value_dim])
):
    """One-token WKV6 recurrence (decode path)."""
    rf, kf, vf = (a.astype(jnp.float32) for a in (r, k, v))
    kv = jnp.einsum("bhn,bhm->bhnm", kf, vf)
    y = jnp.einsum("bhn,bhnm->bhm", rf, state + u[..., None] * kv)
    state = state * jnp.exp(logw)[..., None] + kv
    return y, state


def time_mix(
    p: dict,
    x: jax.Array,              # (B, T, D)
    x_prev: jax.Array,         # (B, D) carry from previous token (decode) or zeros
    state0: jax.Array | None,
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
):
    """Full-sequence time-mix. Returns (out, (last_x, final_state))."""
    b, t, d = x.shape
    r_cfg = cfg.rwkv
    h = d // r_cfg.head_dim
    shifted = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)
    xw, xk, xv, xr, xg = _ddlerp(p, x, shifted)
    rr = (xr @ p["wr"]).reshape(b, t, h, r_cfg.head_dim)
    kk = (xk @ p["wk"]).reshape(b, t, h, r_cfg.head_dim)
    vv = (xv @ p["wv"]).reshape(b, t, h, r_cfg.head_dim)
    g = jax.nn.silu(xg @ p["wg"])
    logw = _decay(p, xw).reshape(b, t, h, r_cfg.head_dim)
    if exec_cfg.kernels:
        from repro.kernels import ops as kops

        y, state = kops.rwkv6_wkv(rr, kk, vv, logw, p["u"], state0)
    else:
        y, state = wkv_chunked(rr, kk, vv, logw, p["u"], state0)
    y = y.reshape(b, t, d)
    y = rmsnorm(p["ln_x"], y, cfg.norm_eps).astype(x.dtype) * g
    return y @ p["wo"], (x[:, -1], state)


def time_mix_step(p: dict, x: jax.Array, x_prev: jax.Array, state: jax.Array, cfg: ModelConfig):
    """One-token time-mix. x: (B, D)."""
    b, d = x.shape
    r_cfg = cfg.rwkv
    h = d // r_cfg.head_dim
    xw, xk, xv, xr, xg = _ddlerp(p, x, x_prev)
    rr = (xr @ p["wr"]).reshape(b, h, r_cfg.head_dim)
    kk = (xk @ p["wk"]).reshape(b, h, r_cfg.head_dim)
    vv = (xv @ p["wv"]).reshape(b, h, r_cfg.head_dim)
    g = jax.nn.silu(xg @ p["wg"])
    logw = _decay(p, xw).reshape(b, h, r_cfg.head_dim)
    y, state = wkv_step(rr, kk, vv, logw, p["u"], state)
    y = rmsnorm(p["ln_x"], y.reshape(b, d), cfg.norm_eps).astype(x.dtype) * g
    return y @ p["wo"], x, state


def channel_mix(p: dict, x: jax.Array, x_prev: jax.Array):
    """RWKV channel-mix. x: (B, T, D), x_prev: (B, D). Returns (out, last_x)."""
    shifted = jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)
    xx = shifted - x
    xk = x + xx * p["mu_k"].astype(x.dtype)
    xr = x + xx * p["mu_r"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (kk @ p["wv"]), x[:, -1]


def channel_mix_step(p: dict, x: jax.Array, x_prev: jax.Array):
    xx = x_prev - x
    xk = x + xx * p["mu_k"].astype(x.dtype)
    xr = x + xx * p["mu_r"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (kk @ p["wv"]), x
