"""Serve Yi-6B at its published widths on one TPU chip, and check the result.

    python3 chip_smoke.py [--seed N]

Builds the model and the engine with `make_model` and `build_engine`, the
functions `python -m repro.launch.serve` builds them with, at Yi-6B's
published configuration (32 layers, d_model 4096, d_ff 11008, 32 query and
4 KV heads of 128, vocab 64000, bf16; weights random from --seed). Then,
in this one process, each phase runs to completion:

  (a) standalone, continuous batching: a few requests of fixed prompt
      length, then the same phase again, which must compile nothing new;
      then the served logits of one request, at the prompt's last position
      and at the first decode step, against `backbone.forward` without
      kernels and without a cache;
  (b) standalone, serialized batching (the launcher's default);
  (c) dpd (disaggregated prefill/decode), continuous batching; both
      logical pools live on the one chip.

It prints the device, each phase's wall time (measured around work that
ends in `block_until_ready`), its compilations, the device's
`peak_bytes_in_use` and the engine's modeled clock, and checks that the
served programs contain the Pallas TPU kernels. Its last line is one JSON
object naming the device. Without a TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.serve import build_engine, enable_compile_cache, make_model  # noqa: E402
from repro.models import backbone  # noqa: E402
from repro.models.layers import ExecConfig  # noqa: E402

ARCH = "yi-6b"
N_REQUESTS = 4
PROMPT_LEN = 240
MAX_NEW = 16

# Relative L2 error allowed between served and reference logits, at the
# prompt's last position and at the decode step alike. Both paths use the
# same bf16 weights and round every activation to bf16 (unit roundoff
# 2**-9), but at different points: the served kernels keep attention
# scores and probabilities in float32 and accumulate them online, the
# reference rounds both to bf16 and reduces in one pass. Each of the 32
# layers adds a relative perturbation of a few roundoffs to the residual
# stream; summed as a random walk that is about sqrt(32) * 4 * 2**-9 =
# 4.4e-2. The same comparison at depth 32 on the CPU backend, kernels
# interpreted, gives 2e-2 to 4e-2 (widths 128 and 1024). A wrong page,
# mask or position moves the logits by far more.
LOGITS_RTOL = 6e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts XLA compilations from JAX's own monitoring events.

    `compiles` counts programs JAX had to obtain an executable for (an
    in-memory cache hit emits nothing); `cache_hits` counts those that the
    persistent compilation cache supplied instead of the compiler."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def require_tpu() -> "tuple[jax.Device, int]":
    """The first device and the device count; exits where it is no TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    return devs[0], len(devs)


def make_prompts(cfg, seed: int, n: int = N_REQUESTS,
                 length: int = PROMPT_LEN) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=length, dtype=np.int32)
            for _ in range(n)]


def serve(cfg, params, kind: str, batching: str, prompts, max_new: int = MAX_NEW,
          seed: int = 0) -> dict:
    """One phase: a fresh engine serves `prompts` to completion. Checks
    that every request finished with `max_new` in-vocabulary tokens, that
    the pool got every block back and, on the paged path, that no dense
    gather ran. Returns the engine and the phase's numbers."""
    engine = build_engine(cfg, params, kind, batching=batching, seed=seed)
    t0 = time.perf_counter()
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    done = engine.run_until_idle()
    jax.block_until_ready((engine.pool.k, engine.pool.v))
    wall_s = time.perf_counter() - t0
    if len(done) != len(prompts) or engine.aborted:
        raise RuntimeError(f"{kind}/{batching}: {len(done)} of {len(prompts)} "
                           f"requests finished, {len(engine.aborted)} aborted")
    for r in done:
        toks = np.asarray(r.out_tokens)
        if len(toks) != max_new or toks.min() < 0 or toks.max() >= cfg.vocab_size:
            raise RuntimeError(f"{kind}/{batching}: request {r.req_id} "
                               f"emitted {toks.tolist()}")
    if engine.pool.free_blocks != engine.pool.num_blocks:
        raise RuntimeError(f"{kind}/{batching}: {engine.pool.free_blocks} of "
                           f"{engine.pool.num_blocks} pool blocks free at idle")
    if engine.paged and engine.pool.gather_calls:
        raise RuntimeError(f"{kind}/{batching}: paged engine gathered the "
                           f"cache {engine.pool.gather_calls} times")
    return {"engine": engine, "wall_s": wall_s, "modeled_s": engine.clock,
            "tokens": sum(len(r.out_tokens) for r in done)}


_reference_forward = jax.jit(backbone.forward, static_argnames=("cfg", "exec_cfg"))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check_logits(cfg, params, prompt, seed: int = 0) -> dict:
    """Served logits against `backbone.forward` over the same tokens.

    The engine serves one request for two tokens while every logits row
    it samples from is recorded: first the prompt's last position, then
    the first decode step. The reference runs the prompt plus the first
    sampled token through `forward` with no kernels and no cache, with the
    same bf16 weights, and is read at the same two positions."""
    engine = build_engine(cfg, params, "standalone", batching="continuous",
                          seed=seed)
    seen = []
    sample = engine._sample

    def recording_sample(logits):
        seen.append(np.asarray(logits[0].astype(jnp.float32)))
        return sample(logits)

    engine._sample = recording_sample
    req = engine.submit(prompt, max_new_tokens=2)
    engine.run_until_idle()
    if len(seen) != 2:
        raise RuntimeError(f"expected 2 sampled logits rows, saw {len(seen)}")
    toks = np.concatenate([prompt, req.out_tokens[:1]]).astype(np.int32)
    ref = _reference_forward(params, {"tokens": jnp.asarray(toks)[None]}, cfg,
                             ExecConfig(use_kernels=False))
    ref = np.asarray(ref[0].astype(jnp.float32))
    n = len(prompt)
    out = {"prefill_rel_err": rel_err(seen[0], ref[n - 1]),
           "decode_rel_err": rel_err(seen[1], ref[n])}
    for row in (*seen, ref[n - 1], ref[n]):
        if row.shape != (cfg.vocab_size,) or not np.isfinite(row).all():
            raise RuntimeError(f"logits row of shape {row.shape} is not "
                               f"({cfg.vocab_size},) and finite")
    for what, err in out.items():
        if err > LOGITS_RTOL:
            raise RuntimeError(f"{what} {err} > {LOGITS_RTOL}")
    return out


def tpu_kernel_calls(engine, prompt_len: int = PROMPT_LEN) -> dict:
    """Pallas TPU kernel calls in the programs of the engine's paged
    decode step and prefill chunk, as lowered for this process's backend.
    A kernel lowers to a `tpu_custom_call` only on a TPU and out of
    interpret mode; a jnp twin lowers to none."""
    cfg, pool = engine.cfg, engine.pool
    nb = pool.blocks_needed(prompt_len + 1)
    decode = jax.jit(backbone.serve_step_paged,
                     static_argnames=("cfg", "exec_cfg", "max_len")).lower(
        engine.params, pool.k, pool.v, jnp.zeros((N_REQUESTS, nb), jnp.int32),
        jnp.full((N_REQUESTS,), prompt_len, jnp.int32),
        jnp.zeros((N_REQUESTS,), jnp.int32), cfg, engine.exec_cfg,
        max_len=prompt_len + 1)
    chunk = jax.jit(backbone.prefill_chunk_paged,
                    static_argnames=("ctx0", "cfg", "exec_cfg")).lower(
        engine.params, pool.k, pool.v, jnp.zeros((1,), jnp.int32), 0,
        jnp.zeros((prompt_len,), jnp.int32), cfg, engine.exec_cfg)
    return {"decode": decode.as_text().count("tpu_custom_call"),
            "chunk": chunk.as_text().count("tpu_custom_call")}


def peak_bytes(device) -> "int | None":
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    device, count = require_tpu()
    cache_dir = enable_compile_cache()
    print(f"device: platform={device.platform} device_kind={device.device_kind} "
          f"count={count}")
    print(f"compile cache: {cache_dir}")
    counter = CompileCounter()
    try:
        def report(name, fn):
            c0, s0, h0 = counter.snapshot()
            t0 = time.perf_counter()
            out = fn()
            wall = time.perf_counter() - t0
            c1, s1, h1 = counter.snapshot()
            print(f"[{name}] wall {wall:.3f} s  compilations {c1 - c0} "
                  f"({s1 - s0:.1f} s, {h1 - h0} from the persistent cache)  "
                  f"peak_bytes_in_use {peak_bytes(device)}")
            return out, c1 - c0

        def init():
            cfg, params = make_model(ARCH, full=True, seed=args.seed)
            jax.block_until_ready(params)
            return cfg, params

        (cfg, params), _ = report("init", init)
        a = cfg.attn
        nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
        print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
              f"d_ff={cfg.d_ff} heads={a.num_heads}/{a.num_kv_heads}x{a.head_dim} "
              f"vocab={cfg.vocab_size} {cfg.dtype}  weights {nbytes / 1e9:.3f} GB "
              f"(published widths, full depth)")
        prompts = make_prompts(cfg, args.seed)
        print(f"workload: {len(prompts)} requests x {PROMPT_LEN} prompt tokens "
              f"x {MAX_NEW} new tokens, all arriving at 0")

        phases = [("a", "standalone", "continuous"),
                  ("a-repeat", "standalone", "continuous"),
                  ("b", "standalone", "serialized"),
                  ("c", "dpd", "continuous")]
        for name, kind, batching in phases:
            res, compiles = report(
                f"phase {name} {kind}/{batching}",
                lambda: serve(cfg, params, kind, batching, prompts, seed=args.seed))
            engine = res["engine"]
            print(f"  tokens {res['tokens']}  serve wall {res['wall_s']:.3f} s  "
                  f"engine clock {res['modeled_s']:.6f} s (modeled)")
            if not engine.paged:
                raise RuntimeError(f"phase {name}: engine took the dense path")
            if name == "a-repeat" and compiles:
                raise RuntimeError(f"repeating phase a compiled {compiles} programs")
            if name == "a":
                calls = tpu_kernel_calls(engine)
                print(f"  Pallas TPU kernel calls per program: {calls}")
                if not all(calls.values()):
                    raise RuntimeError(f"served programs lack a TPU kernel: {calls}")
                errs, _ = report("phase a logits",
                                 lambda: check_logits(cfg, params, prompts[0],
                                                      seed=args.seed))
                print(f"  logits rel L2 err vs reference: prompt position "
                      f"{errs['prefill_rel_err']}  decode step "
                      f"{errs['decode_rel_err']}  (tol {LOGITS_RTOL})")
            del engine, res
    finally:
        counter.close()
    if os.path.isdir(cache_dir):
        print(f"compile cache entries: {len(os.listdir(cache_dir))}")
    print(json.dumps({"ok": True, "device": {"platform": device.platform,
                                             "kind": device.device_kind,
                                             "count": count}}))


if __name__ == "__main__":
    main()
