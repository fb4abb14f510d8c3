"""Plain reference of a Llama-family decoder (Yi follows it exactly).

Straight from the published description: token embedding; per layer
x += Wo . attn(rope(Wq . rms(x)), rope(Wk . rms(x)), Wv . rms(x)) with
grouped-query heads and a causal mask, then x += Wdown . (silu(Wg . rms(x))
* Wu . rms(x)); a final RMSNorm and an untied output head. Rotary
embedding rotates the two halves of each head (the `rotate_half` form of
the published implementation) with base `rope_theta`.

Everything is float32 with matmuls at HIGHEST precision, over weights
given as a parameter tree (those the benchmark made), one layer at a
time so that the float32 copy of one layer is all that is held. It
imports nothing of the program.

`quant="fp8"` computes the same in the next precision below bfloat16:
each linear layer's input and weight are rounded to float8_e4m3fn with a
scale per tensor, as an fp8 serving path would. That is the control that
the comparison has to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _fp8(x):
    """Round to float8_e4m3fn with one scale for the tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, H, D); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer(x, layers, i, m, quant):
    """One decoder layer over x (S, d), float32, causal from position 0."""
    d, h, kvh, hd = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    lw = jax.tree.map(lambda a: a[i], layers)
    s = x.shape[0]
    pos = jnp.arange(s)
    xn = _rms(x, lw["norm1"], eps)
    q = _rope(_mm(xn, lw["attn"]["wq"], quant).reshape(s, h, hd), pos, theta)
    k = _rope(_mm(xn, lw["attn"]["wk"], quant).reshape(s, kvh, hd), pos, theta)
    v = _mm(xn, lw["attn"]["wv"], quant).reshape(s, kvh, hd)
    g = h // kvh
    k = jnp.repeat(k, g, axis=1)                     # (S, H, D), head j uses kv j // g
    v = jnp.repeat(v, g, axis=1)

    def block(qb, start):
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        qpos = start + jnp.arange(qb.shape[0])
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    nq = s // Q_BLOCK
    qs = q.reshape(nq, Q_BLOCK, h, hd)
    o = jax.lax.map(lambda a: block(a[0], a[1]),
                    (qs, jnp.arange(nq) * Q_BLOCK)).reshape(s, h * hd)
    x = x + _mm(o, lw["attn"]["wo"], quant)
    xn = _rms(x, lw["norm2"], eps)
    f = jax.nn.silu(_mm(xn, lw["ffn"]["w_gate"], quant)) * _mm(xn, lw["ffn"]["w_up"], quant)
    return x + _mm(f, lw["ffn"]["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, final_norm, lm_head, m, quant):
    """Logits (N, V) of rows x (N, d)."""
    xn = _rms(x, final_norm, m["rms_norm_eps"])
    return _mm(xn, lm_head.T, quant)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


def _bucket(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def logits_rows(weights: dict, model: dict, tokens, rows, quant: str = "none",
                block: int = 1024):
    """Reference logits (len(rows), V) at positions `rows` of `tokens`.

    The sequence is padded at its end to a multiple of 512 (causal
    attention leaves the earlier positions unchanged), so that one
    compiled program serves every length of a cell's fixed-size mix."""
    import numpy as np
    m = _frozen(model)
    n = len(tokens)
    s = _bucket(n)
    toks = np.zeros(s, np.int32)
    toks[:n] = tokens
    x = _embed(weights["tok"]["embed"], jnp.asarray(toks))
    for i in range(model["num_hidden_layers"]):
        x = _layer(x, weights["layers"], jnp.int32(i), m, quant)
    rows = np.asarray(rows, np.int32)
    out = []
    for a in range(0, len(rows), block):
        idx = np.zeros(block, np.int32)
        part = rows[a:a + block]
        idx[:len(part)] = part
        lg = _head(x[jnp.asarray(idx)], weights["final_norm"],
                   weights["tok"]["lm_head"], m, quant)
        out.append(lg[:len(part)])
    return jnp.concatenate(out, axis=0)


class _Frozen(dict):
    """A hashable view of the model block, for jit's static arguments."""
    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _frozen(model: dict) -> _Frozen:
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta")
    return _Frozen({k: model[k] for k in keep})
