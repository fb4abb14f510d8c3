"""Serving launcher: run the GreenLLM engine end-to-end.

    python -m repro.launch.serve --kind dsd --requests 12 --max-new 24

Uses reduced-config models so the full pipeline (prefill -> paged KV ->
speculative rounds -> verification -> carbon accounting) executes with
real numerics on CPU; on TPU pools the same engine runs the full configs
(--arch/--draft-arch select any registry entry, --full disables the
reduction). `make_model` and `build_engine` are the construction steps
`main` uses; chip_smoke.py at the repository root calls the same two.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.core.carbon import GRID_CI
from repro.core.spec_decode import SpecConfig
from repro.models import ModelConfig, init_params
from repro.serving.engine import ServingEngine
from repro.serving.workload import DATASETS

# the checkout's own compile-cache directory (listed in .gitignore)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already keeps the cache
    there and nothing is changed. Otherwise the cache goes to one fixed
    directory inside the checkout, so a later run of this checkout finds
    what an earlier one compiled. Call it when an entry point's main
    starts, never when a module is imported."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def make_model(arch: str, full: bool, seed: int,
               draft: bool = False) -> "tuple[ModelConfig, dict]":
    """(config, params) of a registry arch, weights random from `seed`.

    `full` keeps the published config; otherwise the CPU-scale reduction
    is used, and a draft model's FFN shrinks further so it stays cheaper
    than its target."""
    cfg = get_config(arch) if full else get_reduced_config(arch)
    if draft and not full:
        cfg = dataclasses.replace(cfg, name=cfg.name + "-draft", d_ff=128)
    return cfg, init_params(jax.random.PRNGKey(seed), cfg)


def build_engine(tcfg: ModelConfig, tparams, kind: str, *, dcfg=None,
                 dparams=None, spec_k: int = 4, new_chip: str = "tpu_v5e",
                 old_chip: str = "tpu_v2", temperature: float = 1.0,
                 seed: int = 0, batching=None) -> ServingEngine:
    """The launcher's engine: the old chip joins only the disaggregated
    kinds (dpd/dsd), and spec/dsd draft `spec_k` tokens per round."""
    return ServingEngine(
        tcfg, tparams, kind=kind, draft_cfg=dcfg, draft_params=dparams,
        spec=SpecConfig(num_draft_tokens=spec_k),
        new_chip=new_chip,
        old_chip=old_chip if kind in ("dpd", "dsd") else None,
        temperature=temperature, seed=seed, batching=batching)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--draft-arch", default="yi-6b")
    ap.add_argument("--kind", default="dsd",
                    choices=["standalone", "spec", "dpd", "dsd"])
    ap.add_argument("--dataset", default="sharegpt", choices=list(DATASETS))
    ap.add_argument("--qps", type=float, default=2.0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--new-chip", default="tpu_v5e")
    ap.add_argument("--old-chip", default="tpu_v2")
    ap.add_argument("--grid", default="ciso", choices=list(GRID_CI))
    ap.add_argument("--full", action="store_true",
                    help="use the full config (TPU-scale; not for CPU)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    tcfg, tparams = make_model(args.arch, args.full, args.seed)
    dcfg = dparams = None
    if args.kind in ("spec", "dsd"):
        dcfg, dparams = make_model(args.draft_arch, args.full, args.seed + 1,
                                   draft=True)
    engine = build_engine(
        tcfg, tparams, args.kind, dcfg=dcfg, dparams=dparams,
        spec_k=args.spec_k, new_chip=args.new_chip, old_chip=args.old_chip,
        temperature=args.temperature, seed=args.seed)

    ds = DATASETS[args.dataset]
    rng = np.random.default_rng(args.seed)
    t_wall = time.time()
    for i in range(args.requests):
        plen = int(np.clip(rng.lognormal(np.log(ds.p50[0]), 0.4), 4, 64))
        prompt = rng.integers(0, tcfg.vocab_size, size=plen)
        engine.submit(prompt, max_new_tokens=args.max_new, arrival_s=i / args.qps)
    done = engine.run_until_idle()
    t_wall = time.time() - t_wall

    ci = GRID_CI[args.grid]
    total_tokens = sum(len(r.out_tokens) for r in done)
    print(f"\n=== {args.kind} on {args.new_chip}"
          + (f"+{args.old_chip}" if args.kind in ("dpd", "dsd") else "") + " ===")
    print(f"requests: {len(done)}  output tokens: {total_tokens}  wall: {t_wall:.1f}s")
    print(f"modeled time: {engine.clock:.3f}s")
    for name, use in engine.use.items():
        print(f"  {name}: busy {use.busy_s:.3f}s energy {use.energy_j:.1f}J")
    if engine.rounds:
        print(f"speculative acceptance (measured): {engine.acceptance_rate:.3f} "
              f"over {engine.rounds} rounds")
    if engine.link_bytes:
        print(f"interconnect traffic: {engine.link_bytes/1e6:.2f} MB")
    ttfts = [r.ttft_s for r in done]
    tpots = [r.tpot_s for r in done if len(r.out_tokens) > 1]
    print(f"modeled TTFT mean {np.mean(ttfts)*1e3:.1f}ms  "
          f"TPOT mean {np.mean(tpots)*1e3:.2f}ms "
          f"(SLO: {ds.ttft_slo_s*1e3:.0f}/{ds.tpot_slo_s*1e3:.0f} ms)")
    from repro.core.carbon import CHIP_DB, request_carbon

    total = sum(
        (request_carbon(u.busy_s, u.energy_j, CHIP_DB[n], ci_g_per_kwh=ci)
         for n, u in engine.use.items()),
        start=request_carbon(0, 0, CHIP_DB[args.new_chip]))
    print(f"carbon: {total.total_g*1e3:.3f} mg total "
          f"({total.operational_g*1e3:.3f} op + {total.embodied_g*1e3:.3f} emb) "
          f"= {total.total_g/max(total_tokens,1)*1e3:.4f} mg/token @ {ci:.0f} gCO2/kWh")


if __name__ == "__main__":
    main()
