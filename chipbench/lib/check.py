"""The comparison that decides `correct` for a served model.

Once the window has closed, a sample of the requests it finished is
drawn from the seed, with the longest of them in it. The plain reference
runs once over each sampled prompt followed by its served tokens, and
the number compared is the widest gap by which a served token's logit
lies below the reference's best logit at that position (logit units of
the float32 reference). Greedy decoding serves the argmax, so a sound
program reads only the rounding of near ties; a token altered where it
is produced, a wrong page, mask or position reads far more.
"""
from __future__ import annotations

import importlib
from typing import Sequence

import numpy as np


def sample(finished: Sequence, seed: int, min_tokens: int, max_requests: int) -> list:
    """The longest finished request, then others drawn from the seed,
    until `min_tokens` served tokens or `max_requests` requests."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-len(r.out_tokens), r.req_id))
    picked = [by_len[0]]
    rest = by_len[1:]
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7]).permutation(len(rest))
    for j in order:
        if sum(len(r.out_tokens) for r in picked) >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[j])
    return picked


def reference_module(name: str):
    return importlib.import_module(f"chipbench.references.{name}")


def served_gaps(ref, weights: dict, model: dict, reqs: Sequence, quant: str = "none") -> dict:
    """For each sampled request, the reference's logits at the positions
    that produced its served tokens. Returns the widest gap of a served
    token below the reference's best, the number of served tokens that
    were not the reference's argmax, and the number compared. With
    `quant` set, the same is read for the token that the lower-precision
    reference puts first at each position (the control)."""
    import jax.numpy as jnp
    widest, differ, n = 0.0, 0, 0
    ctrl_widest, ctrl_differ = 0.0, 0
    for r in reqs:
        out = np.asarray(r.out_tokens, np.int32)
        prompt = np.asarray(r.prompt, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = ref.logits_rows(weights, model, seq, rows)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(out)[:, None], axis=-1)[:, 0]
        gap = np.asarray(best - got)
        widest = max(widest, float(gap.max()))
        differ += int(np.sum(np.asarray(jnp.argmax(lg, axis=-1)) != out))
        n += len(out)
        if quant != "none":
            lq = ref.logits_rows(weights, model, seq, rows, quant=quant)
            pick = jnp.argmax(lq, axis=-1)
            cg = np.asarray(best - jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0])
            ctrl_widest = max(ctrl_widest, float(cg.max()))
            ctrl_differ += int(np.sum(np.asarray(pick) != np.asarray(jnp.argmax(lg, axis=-1))))
    res = {"served_gap": widest, "served_not_argmax": differ, "tokens_compared": n,
           "requests_compared": len(reqs)}
    if quant != "none":
        res.update({"control_gap": ctrl_widest, "control_not_argmax": ctrl_differ})
    return res
