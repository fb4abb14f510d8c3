"""Training step factories: sharded pjit step, microbatch accumulation,
and a compressed-gradient data-parallel variant.

The plain step relies on XLA SPMD for all communication (reduce-scatter /
all-reduce placement chosen by the partitioner from the in/out shardings);
the compressed variant does the data-axis gradient sync explicitly in
shard_map with int8 payloads (distributed/compression.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.compression import compress_tree_mean
from repro.distributed.sharding import (
    batch_pspecs,
    param_pspecs,
    zero_pspecs,
)
from repro.models import backbone
from repro.models.config import ModelConfig
from repro.models.layers import DEFAULT_EXEC, ExecConfig
from repro.training.optimizer import AdamWConfig, apply_updates, init_opt_state


def loss_and_grads(params, batch, cfg: ModelConfig, exec_cfg: ExecConfig,
                   microbatches: int = 1):
    """Value+grad with optional microbatch gradient accumulation."""
    if microbatches <= 1:
        return jax.value_and_grad(backbone.loss_fn)(params, batch, cfg, exec_cfg)

    b = batch["labels"].shape[0]
    assert b % microbatches == 0, (b, microbatches)
    mb = b // microbatches

    def _split(path, x):
        name = str(path[-1].key) if path else ""
        if name == "positions":  # (3, B, S): batch is dim 1
            y = x.reshape(x.shape[0], microbatches, mb, *x.shape[2:])
            return jnp.moveaxis(y, 1, 0)
        return x.reshape(microbatches, mb, *x.shape[1:])

    split = jax.tree_util.tree_map_with_path(_split, batch)

    def one(carry, mbatch):
        loss_acc, g_acc = carry
        loss, g = jax.value_and_grad(backbone.loss_fn)(params, mbatch, cfg, exec_cfg)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), g_acc, g)
        return (loss_acc + loss, g_acc), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), _ = jax.lax.scan(one, (jnp.zeros(()), zero), split)
    inv = 1.0 / microbatches
    return loss * inv, jax.tree.map(lambda g: g * inv, grads)


def train_step(params, opt_state, batch, cfg: ModelConfig,
               opt_cfg: AdamWConfig = AdamWConfig(),
               exec_cfg: ExecConfig = DEFAULT_EXEC,
               microbatches: int = 1):
    loss, grads = loss_and_grads(params, batch, cfg, exec_cfg, microbatches)
    # pin the gradient cross-replica sync to bf16: the optimizer consumes
    # fp32, and without this barrier XLA hoists the upcast above the
    # data-axis all-reduce - 2x the wire bytes (§Perf iteration 4)
    grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
    params, opt_state, metrics = apply_updates(opt_cfg, params, grads, opt_state)
    return params, opt_state, {"loss": loss, **metrics}


def make_sharded_train_step(
    mesh: Mesh,
    cfg: ModelConfig,
    params_like,
    batch_like,
    opt_cfg: AdamWConfig = AdamWConfig(),
    exec_cfg: ExecConfig = DEFAULT_EXEC,
    microbatches: int = 1,
    donate: bool = True,
):
    """jit(train_step) with explicit in/out shardings for `mesh`.

    params: TP-sharded ("model"); optimizer state: additionally ZeRO-sharded
    over the data axes; batch: sharded over ("pod", "data")."""
    pspec = param_pspecs(params_like, mesh)
    zspec = zero_pspecs(params_like, mesh)
    bspec = batch_pspecs(batch_like, mesh)
    opt_spec = {"step": P(), "m": zspec, "v": zspec, "master": zspec}
    metric_spec = {"loss": P(), "grad_norm": P(), "lr": P()}

    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree)
    fn = functools.partial(train_step, cfg=cfg, opt_cfg=opt_cfg,
                           exec_cfg=exec_cfg, microbatches=microbatches)
    return jax.jit(
        fn,
        in_shardings=(ns(pspec), ns(opt_spec), ns(bspec)),
        out_shardings=(ns(pspec), ns(opt_spec), ns(metric_spec)),
        donate_argnums=(0, 1) if donate else (),
    )


def make_compressed_train_step(
    mesh: Mesh,
    cfg: ModelConfig,
    opt_cfg: AdamWConfig = AdamWConfig(),
    exec_cfg: ExecConfig = DEFAULT_EXEC,
    data_axis: str = "data",
):
    """Data-parallel step with explicit int8 gradient all-reduce.

    Params are replicated over `data_axis`; each shard computes grads on
    its batch slice; the sync is the int8 error-feedback all-reduce. State
    carries the per-shard residual."""
    def step(params, opt_state, residual, batch):
        def shard_fn(params, opt_state, residual, batch):
            residual = jax.tree.map(lambda r: r[0], residual)  # drop shard dim
            loss, grads = jax.value_and_grad(backbone.loss_fn)(
                params, batch, cfg, exec_cfg)
            grads, residual = compress_tree_mean(grads, data_axis, residual)
            loss = jax.lax.pmean(loss, data_axis)
            params, opt_state, metrics = apply_updates(opt_cfg, params, grads, opt_state)
            residual = jax.tree.map(lambda r: r[None], residual)
            return params, opt_state, residual, {"loss": loss, **metrics}

        rep = P()
        bspec = jax.tree.map(lambda _: P(data_axis), batch)
        rspec = jax.tree.map(lambda _: P(data_axis), residual)  # per-shard state
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, params),
                      jax.tree.map(lambda _: rep, opt_state),
                      rspec, bspec),
            out_specs=(jax.tree.map(lambda _: rep, params),
                       jax.tree.map(lambda _: rep, opt_state),
                       rspec,
                       {"loss": rep, "grad_norm": rep, "lr": rep}),
            check_vma=False,
        )(params, opt_state, residual, batch)

    return jax.jit(step)


def init_residual(params, mesh: Mesh, data_axis: str = "data"):
    """Per-shard error-feedback residual (stacked over the data axis)."""
    n = mesh.shape[data_axis]
    return jax.tree.map(
        lambda p: jnp.zeros((n, *p.shape), jnp.float32), params)
