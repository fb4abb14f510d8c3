"""Logical-axis sharding rules for every parameter / cache / batch pytree.

Rules are name+shape driven (tree paths), so one function covers all six
architecture families:

  - vocab (embed / lm_head)            -> rows on "model"
  - attention q/k/v projections        -> columns (heads) on "model"
  - attention out / FFN down / out_proj-> rows on "model" (psum after)
  - FFN gate/up, MoE expert FFNs       -> hidden dim on "model"
  - RWKV/Mamba head-structured leaves  -> heads on "model" when divisible
  - small leaves (norm gains, biases, routers, loras, B/C projections)
                                       -> replicated
  - batch dims                         -> ("pod", "data")
  - decode KV caches                   -> sequence on "model" (flash-
    decoding style: most assigned archs have kv_heads not divisible by 16,
    so the robust rule shards the *sequence* and lets XLA insert the
    softmax partial-reduction), batch on data when divisible

ZeRO-style optimizer-state sharding: `zero_variant` adds the data axes to
the first replicated, divisible dimension of each leaf spec.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

# leaves that stay replicated regardless of shape (small / awkward to split)
_REPLICATED_NAMES = {
    "norm", "norm1", "norm2", "final_norm", "ln_x", "router", "mus", "mu_x",
    "mu_k", "mu_r", "w0", "lora_mix_a", "lora_mix_b", "lora_w_a", "lora_w_b",
    "conv_bias_x", "conv_bias_b", "conv_bias_c", "conv_b", "conv_c",
    "w_b", "w_c", "a_log", "dt_bias", "d_skip", "dt", "pos",
}
_ROW_SHARDED = {"embed", "lm_head", "wo", "w_down", "out_proj"}
_COL_SHARDED = {"wq", "wk", "wv", "wr", "wg", "w_gate", "w_up", "w_z", "w_x",
                "w_dt", "conv_x", "u", "wk_cm", "wv_cm"}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def _dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh: Mesh) -> int:
    return int(np.prod([_axis_size(mesh, a) for a in _dp_axes(mesh)]) or 1)


def _leaf_name(path) -> str:
    return "/".join(
        str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)
    )


def _under_layers(path) -> bool:
    return any(isinstance(e, jax.tree_util.DictKey) and e.key == "layers" for e in path)


def _spec_for_param(name: str, shape: tuple[int, ...], mesh: Mesh, stacked: bool) -> P:
    model = _axis_size(mesh, "model")
    dp = _dp_axes(mesh)
    dps = _dp_size(mesh)
    ndim = len(shape)
    lead = 1 if stacked else 0

    base0 = name.split("/")[-1]
    if base0 in ("w_gate", "w_up", "w_down") and ndim - lead == 3:
        # MoE expert banks (E, D, F)/(E, F, D): expert-parallel over the
        # data axes when divisible (llama4: E=16), TP on the hidden dim.
        e_dim, mid, last = lead, lead + 1, lead + 2
        spec: list[Any] = [None] * ndim
        ep = ep_axes_for(mesh, shape[e_dim])
        if ep is not None:
            spec[e_dim] = ep if len(ep) > 1 else ep[0]
        h_dim = last if base0 != "w_down" else mid  # the FFN hidden dim
        if shape[h_dim] % model == 0:
            spec[h_dim] = "model"
        return P(*spec)

    def ok(dim_idx: int) -> bool:
        return shape[dim_idx] % model == 0 and shape[dim_idx] >= 256

    spec: list[Any] = [None] * ndim
    base = name.split("/")[-1]
    if base in _REPLICATED_NAMES or ndim == lead:
        return P(*spec)
    if name.endswith("channel_mix/wv"):
        # RWKV channel-mix down-projection: rows (hidden) on "model"
        if shape[lead] % model == 0:
            spec[lead] = "model"
        return P(*spec)
    if base in ("wk", "wv") and "attn" in name:
        # KV projections: shard heads only when every shard gets >= 1 head
        # (kv_heads >= model); otherwise replicate - the decode cache then
        # shards its *sequence* dim instead (cache_pspecs)
        if shape[-1] % model == 0 and shape[-1] // model >= 128:
            spec[-1] = "model"
        return P(*spec)
    if base in _ROW_SHARDED:
        # shard the first non-stack dim (rows); MoE w_down (E, F, D) -> F
        i = lead if shape[lead] % model == 0 and len(shape) - lead >= 2 else None
        if base == "w_down" and ndim - lead == 3:
            i = lead + 1
        if base in ("embed", "lm_head"):
            i = 0
        if i is not None and shape[i] % model == 0:
            spec[i] = "model"
        return P(*spec)
    if base in _COL_SHARDED:
        if shape[-1] % model == 0 and (shape[-1] >= 128 or base == "u"):
            spec[-1] = "model"
        return P(*spec)
    # default: replicate 1-D, column-shard >=2-D when divisible and large
    if ndim - lead >= 2 and ok(ndim - 1):
        spec[-1] = "model"
    return P(*spec)


def ep_axes_for(mesh: Mesh, num_experts: int):
    """Expert-parallel axes: the largest data-axes subset dividing E."""
    for axes in (("pod", "data"), ("data",), ("pod",)):
        if all(a in mesh.axis_names for a in axes):
            size = int(np.prod([_axis_size(mesh, a) for a in axes]))
            if size > 1 and num_experts % size == 0:
                return axes
    return None


def param_pspecs(params, mesh: Mesh):
    """PartitionSpec pytree matching a params pytree."""

    def assign(path, leaf):
        return _spec_for_param(_leaf_name(path), leaf.shape, mesh, _under_layers(path))

    return jax.tree_util.tree_map_with_path(assign, params)


def param_shardings(params, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(params, mesh))


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------
def batch_pspecs(batch, mesh: Mesh):
    """Shard global-batch dims over ("pod", "data")."""
    dp = _dp_axes(mesh)
    dps = _dp_size(mesh)

    def assign(path, leaf):
        name = _leaf_name(path)
        if name == "positions":                     # (3, B, S)
            return P(None, dp, None) if leaf.shape[1] % dps == 0 else P()
        b = leaf.shape[0]
        rest = [None] * (leaf.ndim - 1)
        if b % dps == 0:
            return P(dp, *rest)
        return P(None, *rest)

    return jax.tree_util.tree_map_with_path(assign, batch)


def cache_pspecs(cache, cfg: ModelConfig, mesh: Mesh):
    """Decode-cache sharding. KV caches shard sequence on "model" and batch
    on the data axes when divisible; recurrent state shards heads on
    "model". Falls back to spreading the sequence over every axis for the
    B=1 long-context cells."""
    dp = _dp_axes(mesh)
    dps = _dp_size(mesh)
    model = _axis_size(mesh, "model")

    def assign(path, leaf):
        name = _leaf_name(path)
        if name == "pos":
            return P()
        if name in ("k", "v"):                      # (L, B, KV, S, hd)
            _, b, _, s, _ = leaf.shape
            if b % dps == 0 and s % model == 0:
                return P(None, dp, None, "model", None)
            if s % (dps * model) == 0:              # long-context, B == 1
                return P(None, None, None, dp + ("model",), None)
            return P()
        if name == "state":                         # rwkv (L, B, H, N, N)
            h = leaf.shape[2]
            bspec = dp if leaf.shape[1] % dps == 0 else None
            return P(None, bspec, "model" if h % model == 0 else None, None, None)
        if name == "ssm_state":                     # (L, B, H, N, P)
            h = leaf.shape[2]
            bspec = dp if leaf.shape[1] % dps == 0 else None
            return P(None, bspec, "model" if h % model == 0 else None, None, None)
        if name == "conv_state":                    # (L, B, W-1, C) mixed segs
            bspec = dp if leaf.shape[1] % dps == 0 else None
            return P(None, bspec, None, None)
        if name in ("x_prev_att", "x_prev_ffn"):    # (L, B, D)
            bspec = dp if leaf.shape[1] % dps == 0 else None
            return P(None, bspec, None)
        return P()

    return jax.tree_util.tree_map_with_path(assign, cache)


def tokens_pspec(tokens_shape, mesh: Mesh):
    dp = _dp_axes(mesh)
    if tokens_shape[0] % _dp_size(mesh) == 0:
        return P(dp, *([None] * (len(tokens_shape) - 1)))
    return P(*([None] * len(tokens_shape)))


# ---------------------------------------------------------------------------
# ZeRO-style optimizer-state sharding
# ---------------------------------------------------------------------------
def zero_variant(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Add the data axes to the first replicated, divisible dim of `spec`."""
    dp = _dp_axes(mesh)
    dps = _dp_size(mesh)
    if dps == 1:
        return spec
    used = {a for e in spec if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if used & set(dp):   # already (expert-)sharded over the data axes
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, n) in enumerate(zip(parts, shape)):
        if p is None and n % dps == 0 and n >= dps:
            parts[i] = dp
            return P(*parts)
    return P(*parts)


def zero_pspecs(params, mesh: Mesh):
    specs = param_pspecs(params, mesh)
    return jax.tree.map(
        lambda leaf, s: zero_variant(s, leaf.shape, mesh), params, specs)
