"""Random weights for a decoder of the Llama family, made by the benchmark.

The benchmark makes the weights itself, from the run's seed, so that the
reference it checks the program against takes nothing the program made.
They are built on the device in one jitted call, in the type they are
served in, and laid out as the program's parameter tree; `check_layout`
refuses a tree that differs from what the program's own initialiser
would build, so a change of layout in the program is an error here and
not a silent mismatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                              (int(seed) >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dims",))
def make_weights(key: jax.Array, dims: tuple) -> dict:
    """The parameter tree of a Llama-family decoder.

    dims = (layers, d_model, d_ff, heads, kv_heads, head_dim, vocab, dtype).
    Matrices are normal with variance 1/fan_in, so every layer keeps the
    residual stream at unit scale; the embedding is unit normal; the norm
    gains are 1 + N(0, 0.1**2), so a path that ignored them would show."""
    L, d, f, h, kvh, hd, v, dtype = dims
    dt = jnp.dtype(dtype)
    ks = jax.random.split(key, 12)
    qd, kd = h * hd, kvh * hd

    def gain(k, shape):
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

    return {
        "tok": {"embed": _normal(ks[0], (v, d), 1.0, dt),
                "lm_head": _normal(ks[1], (v, d), d ** -0.5, dt)},
        "layers": {
            "norm1": gain(ks[2], (L, d)),
            "norm2": gain(ks[3], (L, d)),
            "attn": {"wq": _normal(ks[4], (L, d, qd), d ** -0.5, dt),
                     "wk": _normal(ks[5], (L, d, kd), d ** -0.5, dt),
                     "wv": _normal(ks[6], (L, d, kd), d ** -0.5, dt),
                     "wo": _normal(ks[7], (L, qd, d), qd ** -0.5, dt)},
            "ffn": {"w_gate": _normal(ks[8], (L, d, f), d ** -0.5, dt),
                    "w_up": _normal(ks[9], (L, d, f), d ** -0.5, dt),
                    "w_down": _normal(ks[10], (L, f, d), f ** -0.5, dt)},
        },
        "final_norm": gain(ks[11], (d,)),
    }


def dims_of(model: dict) -> tuple:
    """The static dims tuple of a configuration file's `model` block."""
    return (model["num_hidden_layers"], model["hidden_size"],
            model["intermediate_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["vocab_size"], model["torch_dtype"])


def check_layout(weights_shape, program_shape) -> None:
    """Raise where the benchmark's tree differs from the program's."""
    def desc(tree):
        return {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_leaves_with_path(tree)}
    ours, theirs = desc(weights_shape), desc(program_shape)
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"parameter layout differs from the program's: {diff[:8]}")
