"""Shared neural-net building blocks: RMSNorm, RoPE / M-RoPE, SwiGLU, MoE.

All modules are pure functions over explicit parameter pytrees:
`init_*(rng, cfg) -> params` and `apply(params, x, ...) -> y`. Layer stacks
live in `backbone.py`; blocked attention in `attention.py`.

Conventions
-----------
- Activations flow in `cfg.dtype` (bf16 by default); reductions that need
  range (softmax, norms, router) are computed in fp32 and cast back.
- Every init uses truncated-normal-ish scaled init; exact init statistics
  are not a paper contribution, determinism is (seeded PRNG keys).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import AttentionConfig, ModelConfig, MoEConfig


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution-mode knobs threaded through model code.

    static_unroll=True replaces `lax.scan` layer/Q-block loops with Python
    loops so that XLA cost analysis counts every iteration (the "cost"
    dry-run mode); scan mode keeps the HLO small (the "proof" mode and
    real execution).
    """

    static_unroll: bool = False
    q_block: int = 1024          # attention query-block length
    # route hot ops through the Pallas kernels; None lets the platform
    # decide (kernels on TPU, the jnp reference elsewhere - see `kernels`)
    use_kernels: Optional[bool] = None
    remat: bool = True           # checkpoint scan bodies during training
    moe_group_size: int = 4096   # tokens per MoE dispatch group
    # Megatron-style sequence parallelism: PartitionSpec entries (as a
    # tuple) to constrain the (B, S, D) residual stream at layer
    # boundaries, e.g. (("pod", "data"), "model", None). Shards the saved
    # remat carries over the model axis (16x activation-memory reduction
    # on the production mesh - EXPERIMENTS.md §Perf iteration 2).
    carry_spec: tuple | None = None
    # Expert-parallel axes for the MoE expert dim (must divide num_experts;
    # set by the launch factories from the mesh). When set, the dispatch
    # buffers are re-laid out expert-major (one all-to-all each way) so the
    # expert FFN einsum is fully local - without it XLA all-gathers the
    # expert weight banks every layer (EXPERIMENTS.md §Perf iteration 4).
    ep_axes: tuple | None = None

    @property
    def kernels(self) -> bool:
        """Whether hot ops run the Pallas kernels: `use_kernels` when set,
        else exactly when JAX's default backend is a TPU."""
        if self.use_kernels is None:
            return jax.default_backend() == "tpu"
        return self.use_kernels


DEFAULT_EXEC = ExecConfig()


def constrain_carry(x, exec_cfg: "ExecConfig"):
    if exec_cfg.carry_spec is None:
        return x
    from jax.sharding import PartitionSpec

    return jax.lax.with_sharding_constraint(x, PartitionSpec(*exec_cfg.carry_spec))


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int) -> jax.Array:
    return jnp.ones((d,), jnp.float32)


def rmsnorm(g: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """fp32 only inside the mean-square reduction; the normalize multiply
    stays in the input dtype. Upcasting the whole tensor would make the
    surrounding sequence-parallel collectives (and their cotangents) run
    in fp32 - 2x the wire bytes (EXPERIMENTS.md §Perf iteration 4)."""
    dt = x.dtype
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(ms + eps).astype(dt)
    return x * scale * g.astype(dt)


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------
def rope_angles(
    positions: jax.Array,          # (..., S) int32 or (3, ..., S) for m-rope
    head_dim: int,
    theta: float,
    m_rope_sections: Optional[tuple[int, int, int]] = None,
):
    """Return (sin, cos) of shape (..., S, head_dim/2), fp32.

    For M-RoPE (qwen2-vl), `positions` has a leading axis of 3 (temporal,
    height, width) and the rotary frequencies are split into the three
    sections: frequency i uses the position stream of its section.
    """
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if m_rope_sections is None:
        ang = positions.astype(jnp.float32)[..., None] * inv_freq
        return jnp.sin(ang), jnp.cos(ang)
    t, h, w = m_rope_sections
    assert t + h + w == half, f"m_rope sections {m_rope_sections} != {half}"
    # section id per frequency: 0 for temporal, 1 height, 2 width
    sec = jnp.concatenate(
        [jnp.zeros((t,), jnp.int32), jnp.ones((h,), jnp.int32), 2 * jnp.ones((w,), jnp.int32)]
    )
    # positions: (3, ..., S) -> (..., S, half) selecting stream per freq
    pos = jnp.moveaxis(positions, 0, -1).astype(jnp.float32)  # (..., S, 3)
    pos_per_freq = jnp.take(pos, sec, axis=-1)                # (..., S, half)
    ang = pos_per_freq * inv_freq
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, S, H, D). sin/cos: (B, S, D/2) or (S, D/2)."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if sin.ndim == 2:
        s = sin[None, :, None, :]
        c = cos[None, :, None, :]
    else:
        s = sin[:, :, None, :]
        c = cos[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_swiglu(rng: jax.Array, d: int, f: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = d ** -0.5
    s_out = f ** -0.5
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype),
    }


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (routed top-k + optional shared experts)
#
# Sort-free capacity dispatch: tokens are grouped (group = `moe_group_size`
# contiguous tokens), each (token, k) unit is assigned a slot
# `expert * C + rank` where rank is the unit's arrival order within its
# expert (computed with a scatter-add bincount + argsort rank), units with
# rank >= C are dropped (standard capacity dropping). Expert FFNs then run
# as one batched einsum over (G, E, C, D) - no (T, E, C) one-hot tensors,
# so memory stays O(tokens) and FLOPs stay O(active params).
# ---------------------------------------------------------------------------
def init_moe(rng: jax.Array, cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dtype = _dtype(cfg)
    k_router, k_e1, k_e2, k_e3, k_sh = jax.random.split(rng, 5)
    s_in = d ** -0.5
    s_out = m.d_ff_expert ** -0.5
    p = {
        "router": (jax.random.normal(k_router, (d, m.num_experts)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k_e1, (m.num_experts, d, m.d_ff_expert)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k_e2, (m.num_experts, d, m.d_ff_expert)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k_e3, (m.num_experts, m.d_ff_expert, d)) * s_out).astype(dtype),
    }
    if m.num_shared_experts > 0:
        p["shared"] = init_swiglu(k_sh, d, m.d_ff_shared, dtype)
    return p


def _moe_capacity(tokens_per_group: int, m: MoEConfig) -> int:
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.num_experts)
    return max(c, m.top_k)


def moe_ffn(
    p: dict,
    x: jax.Array,                 # (B, S, D)
    cfg: ModelConfig,
    exec_cfg: ExecConfig = DEFAULT_EXEC,
) -> jax.Array:
    m = cfg.moe
    b, s, d = x.shape
    total = b * s
    tg = min(exec_cfg.moe_group_size, total)
    assert total % tg == 0, f"tokens {total} not divisible by group {tg}"
    g = total // tg

    # Sharding anchors: groups are data-local by construction (contiguous
    # token blocks), so pin the group dim to the batch axes and the expert
    # FFN hidden dim to "model". Without these, SPMD propagation through
    # the dispatch scatter replicates the expert buffers (167 GiB/device on
    # llama4-scout train_4k - EXPERIMENTS.md §Perf iteration 3).
    if exec_cfg.carry_spec is not None:
        from jax.sharding import PartitionSpec as P

        dp, tp = exec_cfg.carry_spec[0], exec_cfg.carry_spec[1]
        dp = dp if isinstance(dp, tuple) else (dp,)
        gspec = dp if g % 32 == 0 else None  # divisible by dp on both meshes
        ep = exec_cfg.ep_axes
        anchor2 = lambda t: jax.lax.with_sharding_constraint(t, P(gspec, None, None))
        if ep is not None:
            # expert-major layout: experts on the EP axes, expert FFN local.
            # When EP uses only part of the batch axes, the group dim keeps
            # the rest - leaving an axis unused replicates the buffers
            # across it (§Perf iteration 7: 57 GiB on multi-pod qwen2-moe).
            rest = tuple(a for a in (gspec or ()) if a not in ep) or None
            anchor_h = lambda t: jax.lax.with_sharding_constraint(t, P(rest, ep, None, tp))
            anchor_o = lambda t: jax.lax.with_sharding_constraint(t, P(rest, ep, None, None))
        else:
            anchor_h = lambda t: jax.lax.with_sharding_constraint(t, P(gspec, None, None, tp))
            anchor_o = lambda t: jax.lax.with_sharding_constraint(t, P(gspec, None, None, None))
    else:
        anchor2 = anchor_h = anchor_o = lambda t: t

    xg = anchor2(x.reshape(g, tg, d))

    # --- routing (fp32 on the small (T, E) logits only) ---
    logits = (xg @ p["router"].astype(xg.dtype)).astype(jnp.float32)  # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, m.top_k)              # (G, Tg, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renormalize

    tk = tg * m.top_k
    flat_e = expert_idx.reshape(g, tk)                            # (G, TK)
    cap = _moe_capacity(tg, m)

    # All group-local scatter/gathers are vmapped 1-D ops: vmap emits
    # operand-batching dims that GSPMD partitions trivially over the
    # group axis (explicit iota-index scatters got replicated instead -
    # EXPERIMENTS.md §Perf iteration 3).
    # rank of each (token, k) unit within its expert, via stable argsort
    sort_idx = jnp.argsort(flat_e, axis=-1, stable=True)          # (G, TK)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
    counts = jax.vmap(
        lambda fe: jnp.zeros((m.num_experts,), jnp.int32).at[fe].add(1))(flat_e)
    offsets = jnp.cumsum(counts, axis=-1) - counts                # exclusive
    rank_sorted = jnp.arange(tk)[None, :] - jnp.take_along_axis(offsets, sorted_e, axis=-1)
    # invert the permutation: rank[sort_idx[j]] = rank_sorted[j]
    rank = jax.vmap(
        lambda si, rs: jnp.zeros((tk,), jnp.int32).at[si].set(rs))(sort_idx, rank_sorted)

    keep = rank < cap                                             # capacity drop
    slot = jnp.where(keep, flat_e * cap + rank, m.num_experts * cap)  # overflow slot

    # --- dispatch: scatter tokens into (G, E*C (+1 overflow), D) buffers ---
    token_of_unit = jnp.arange(tk) // m.top_k                     # (TK,)
    xu = jnp.take(xg, token_of_unit, axis=1)                      # (G, TK, D)
    buf = anchor2(jax.vmap(
        lambda sl, xr: jnp.zeros((m.num_experts * cap + 1, d), xg.dtype).at[sl].set(xr)
    )(slot, xu))
    ein = anchor_o(buf[:, : m.num_experts * cap].reshape(g, m.num_experts, cap, d))

    # --- expert computation: batched swiglu over experts ---
    hgate = anchor_h(jnp.einsum("gecd,edf->gecf", ein, p["w_gate"]))
    hup = anchor_h(jnp.einsum("gecd,edf->gecf", ein, p["w_up"]))
    hout = anchor_o(jnp.einsum("gecf,efd->gecd", jax.nn.silu(hgate) * hup, p["w_down"]))
    hflat = anchor2(jnp.concatenate(
        [hout.reshape(g, m.num_experts * cap, d), jnp.zeros((g, 1, d), hout.dtype)], axis=1
    ))

    # --- combine: gather each unit's expert output, weight by gate ---
    out_u = anchor2(jax.vmap(lambda hf, sl: jnp.take(hf, sl, axis=0))(hflat, slot))
    w = (gate.reshape(g, tk) * keep).astype(out_u.dtype)
    out = (out_u * w[..., None]).reshape(g, tg, m.top_k, d).sum(axis=2)

    if m.num_shared_experts > 0:
        out = out + swiglu(p["shared"], xg)
    return out.reshape(b, s, d)


def moe_aux_loss(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style f*P dot product).

    The router matmul runs in the activation dtype and only the tiny
    (T, E) logits are upcast - upcasting the (T, D) activations would
    put a second fp32 consumer on the embedding output and drag every
    residual-stream cotangent (and its collectives) to fp32."""
    m = cfg.moe
    d = cfg.d_model
    logits = (x.reshape(-1, d) @ p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, m.num_experts, dtype=jnp.float32), axis=0)
    imp = jnp.mean(probs, axis=0)
    return m.num_experts * jnp.sum(frac * imp)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------
def init_embed(rng: jax.Array, cfg: ModelConfig) -> dict:
    dtype = _dtype(cfg)
    k1, k2 = jax.random.split(rng)
    p = {"embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (
            jax.random.normal(k2, (cfg.vocab_size, cfg.d_model)) * cfg.d_model ** -0.5
        ).astype(dtype)
    return p


def embed_tokens(p: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embed"], tokens, axis=0)


def lm_logits(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = p.get("lm_head", p["embed"])
    return x @ w.T
