"""Microbenchmark + roofline calibration for the paged attention path.

Times real decode and chunked-prefill steps (the engine's paged hot path:
`serve_step_paged` / `prefill_chunk_paged` against a live `PagedKVPool`)
across a batch x context x chunk grid on THIS host, measures the host's
own achievable matmul FLOP/s and memory bandwidth, and least-squares fits
the serving perfmodel's roofline constants

    t_step = overhead + max(flops / (peak * eff_flops),
                            bytes / (bw * eff_bw))

to the measured times. The fit (and the raw grid) goes into the committed
artifact `benchmarks/artifacts/kernel_calibration.json`;
`perfmodel.calibrated()` loads it and `tests/test_calibration.py` pins
`hybrid_step_cost` predictions to the measured times within the artifact's
stated tolerance band - so a perfmodel formula change that silently
de-anchors predictions from measurement fails CI.

Also reports the paged-vs-dense decode wall-clock comparison: the dense
path gathers every sequence contiguous and scatters the whole cache back
each step; the paged path reads pages through block tables and appends one
token. The win must show at batch >= 8 (the PR's acceptance gate; checked
on full runs, reported on --quick).

--quick (CI): shrinks the grid and additionally validates the Pallas
kernels in interpret mode against the jnp twins before timing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_reduced_config  # noqa: E402
from repro.core.carbon import ChipSpec  # noqa: E402
from repro.models import backbone  # noqa: E402
from repro.serving import perfmodel  # noqa: E402
from repro.serving.kv_cache import PagedKVPool  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")
BLOCK_SIZE = 8
POOL_BLOCKS = 2048
SEED = 0


def _bench(fn, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock of fn() in seconds (fn must block on its result)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measure_host_chip(quick: bool = False) -> dict:
    """Achievable peak FLOP/s (bf16 matmul) and memory bandwidth (device
    copy) of whatever backend is running this script. These are the
    `peak_flops` / `hbm_bandwidth` the fitted eff_* fractions are relative
    to - together they reproduce the measured step times."""
    n = 512 if quick else 1024
    a = jnp.ones((n, n), jnp.bfloat16)
    b = jnp.ones((n, n), jnp.bfloat16)
    mm = jax.jit(lambda x, y: x @ y)
    t_mm = _bench(lambda: mm(a, b).block_until_ready())
    peak = 2.0 * n ** 3 / t_mm

    m = (32 if quick else 128) * 2 ** 20 // 4
    src = jnp.ones((m,), jnp.float32)
    cp = jax.jit(lambda x: x + 1.0)
    t_cp = _bench(lambda: cp(src).block_until_ready())
    bw = 2.0 * m * 4 / t_cp                       # read + write
    return {"backend": jax.default_backend(), "matmul_n": n,
            "peak_flops": peak, "bandwidth": bw}


def host_chip_spec(host: dict) -> ChipSpec:
    return ChipSpec(name="host", role="new", peak_flops=host["peak_flops"],
                    hbm_bandwidth=host["bandwidth"], hbm_capacity=16e9,
                    max_power_w=100.0, idle_power_w=20.0, embodied_kg=10.0,
                    year=2024)


def _setup(cfg, batch: int, ctx: int):
    """A pool with `batch` sequences of `ctx` cached tokens + params."""
    params = backbone.init_params(jax.random.PRNGKey(SEED), cfg)
    pool = PagedKVPool(cfg, POOL_BLOCKS, BLOCK_SIZE, dtype=jnp.dtype(cfg.dtype))
    rng = np.random.default_rng(SEED)
    sids = list(range(batch))
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size - 1, size=(batch, ctx)),
                       jnp.int32)
    for i in sids:
        _, cache = backbone.prefill(params, {"tokens": toks[i][None]}, cfg)
        pool.allocate(i, ctx)
        pool.scatter([i], cache["k"], cache["v"])
    return params, pool, sids


def time_decode_step(cfg, batch: int, ctx: int) -> dict:
    """One decode iteration, paged vs dense-gather. The model forward is
    jitted (as the engine's steady state would be); the page/gather data
    movement around it runs as the engine runs it - the paged path's win
    IS skipping the gather + full-cache scatter."""
    params, pool, sids = _setup(cfg, batch, ctx)
    tokens = jnp.arange(1, batch + 1, dtype=jnp.int32)
    lengths = [ctx] * batch
    lengths_j = jnp.asarray(lengths, jnp.int32)
    max_len = ctx + 1
    nb = pool.blocks_needed(max_len)
    for s in sids:                                # pre-grow the tail block
        pool.extend(s, 1)

    paged_fwd = jax.jit(lambda pk, pv, tb: backbone.serve_step_paged(
        params, pk, pv, tb, lengths_j, tokens, cfg, max_len=max_len))
    dense_fwd = jax.jit(lambda k, v: backbone.serve_step(
        params, {"k": k, "v": v, "pos": lengths_j}, tokens, cfg))

    def paged():
        tables = pool.device_tables(sids, nb)
        logits, kt, vt = paged_fwd(pool.k, pool.v, tables)
        pool.scatter_append(sids, kt, vt, lengths)
        return logits.block_until_ready()

    def dense():
        k, v = pool.gather(sids, max_len)
        logits, cache = dense_fwd(k, v)
        pool.scatter(sids, cache["k"], cache["v"])
        return logits.block_until_ready()

    t_paged = _bench(paged)
    t_dense = _bench(dense)
    return {"batch": batch, "ctx": ctx, "paged_s": t_paged, "dense_s": t_dense,
            "speedup": t_dense / t_paged}


def time_prefill_chunk(cfg, chunk: int, ctx0: int) -> dict:
    """One fused chunked-prefill step of a single sequence against ctx0
    cached tokens."""
    params, pool, _ = _setup(cfg, 1, max(ctx0, 1))
    if ctx0 == 0:
        pool.free(0)
        pool.allocate(0, chunk)
    else:
        pool.extend(0, chunk)
    table = pool.device_tables([0], max(pool.blocks_needed(ctx0), 1))[0]
    toks = jnp.arange(1, chunk + 1, dtype=jnp.int32)
    fwd = jax.jit(lambda pk, pv, tb, tk: backbone.prefill_chunk_paged(
        params, pk, pv, tb, ctx0, tk, cfg))

    def step():
        logits, kc, vc = fwd(pool.k, pool.v, table, toks)
        return logits.block_until_ready()

    return {"chunk": chunk, "ctx0": ctx0, "paged_s": _bench(step)}


def _best_overhead(pts):
    """min over oh >= 0 of max_i |raw_i + oh - t_i| / t_i.

    The objective is a max of V-shaped piecewise-linear terms, so the
    optimum sits at a vertex (t_i - raw_i) or a crossing; vertices plus a
    dense sweep of the bracket gets within noise for free."""
    verts = sorted({max(t - x, 0.0) for x, t in pts} | {0.0})
    cands = np.unique(np.concatenate(
        [verts, np.linspace(verts[0], verts[-1], 256)]))
    best_oh, best_err = 0.0, float("inf")
    for oh in cands:
        err = max(abs(x + oh - t) / t for x, t in pts)
        if err < best_err:
            best_err, best_oh = err, float(oh)
    return best_oh, best_err


def fit_calibration(cfg, host: dict, decode_rows, prefill_rows) -> dict:
    """Joint fit of (eff_flops, eff_bw, per-kind overheads) by minimising
    the worst-case relative error of the EXACT prediction formula
    `max(flops/(peak*eff_f), bytes/(bw*eff_b)) + overhead` over every
    measured grid point. Fitting the same max() the roofline predicts
    (rather than a per-knob linear regression) matters because a grid
    point can sit on either side of the ridge depending on the very
    constants being fitted. flop/byte counts come from
    `hybrid_step_cost` itself, so the fit is consistent with what
    `tests/test_calibration.py` re-predicts from the artifact."""
    chip = host_chip_spec(host)
    rows = []
    for r in decode_rows:
        c = perfmodel.hybrid_step_cost(cfg, chip, (), (r["ctx"],) * r["batch"])
        rows.append(("decode", c.flops, c.bytes_hbm, r["paged_s"]))
    for r in prefill_rows:
        c = perfmodel.hybrid_step_cost(cfg, chip, ((r["chunk"], r["ctx0"]),))
        rows.append(("prefill", c.flops, c.bytes_hbm, r["paged_s"]))
    peak, bw = host["peak_flops"], host["bandwidth"]
    effs = np.geomspace(0.01, 1.0, 33)
    best = None
    for ef in effs:
        for eb in effs:
            worst, ohs = 0.0, {}
            for kind in ("decode", "prefill"):
                pts = [(max(f / (peak * ef), b / (bw * eb), 1e-9), t)
                       for k, f, b, t in rows if k == kind]
                oh, err = _best_overhead(pts)
                ohs[kind] = oh
                worst = max(worst, err)
            if best is None or worst < best[0]:
                best = (worst, float(ef), float(eb), ohs)
    _, eff_flops, eff_bw, ohs = best
    return {
        "eff_flops": eff_flops,
        "eff_bw": eff_bw,
        "prefill_overhead_s": ohs["prefill"],
        "decode_overhead_s": ohs["decode"],
    }


def predict(cfg, host: dict, calib: dict, decode_rows, prefill_rows):
    """Re-predict every measured grid point under the fitted constants.
    tests/test_calibration.py re-runs exactly this from the artifact."""
    chip = host_chip_spec(host)
    preds = []
    with perfmodel.calibrated(perfmodel.Calibration(**calib)):
        for r in decode_rows:
            c = perfmodel.hybrid_step_cost(cfg, chip, (),
                                           (r["ctx"],) * r["batch"])
            preds.append({"kind": "decode", "batch": r["batch"],
                          "ctx": r["ctx"], "measured_s": r["paged_s"],
                          "predicted_s": c.time_s})
        for r in prefill_rows:
            c = perfmodel.hybrid_step_cost(cfg, chip,
                                           ((r["chunk"], r["ctx0"]),))
            preds.append({"kind": "prefill", "chunk": r["chunk"],
                          "ctx0": r["ctx0"], "measured_s": r["paged_s"],
                          "predicted_s": c.time_s})
    return preds


def validate_kernels_interpret() -> None:
    """Interpret-mode Pallas vs the jnp twins (CI numerics gate)."""
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    B, H, KV, D, bs, NBp = 2, 4, 2, 32, 8, 10
    r = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    kp, vp = r(NBp, KV, bs, D), r(NBp, KV, bs, D)
    tables = jnp.asarray([[0, 1, 9], [2, 3, 4]], jnp.int32)
    lengths = jnp.asarray([11, 20], jnp.int32)
    q, kn, vn = r(B, 1, H, D), r(B, 1, KV, D), r(B, 1, KV, D)
    a = ops.paged_decode_attention(q, kp[None], vp[None], 0, tables,
                                   lengths, kn, vn, max_len=21, impl="jnp")
    b = ops.paged_decode_attention(q, kp[None], vp[None], 0, tables,
                                   lengths, kn, vn, max_len=21, impl="pallas")
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    assert err < 2e-2, f"paged decode interpret mismatch: {err}"
    qc, ks, vs = r(1, 5, H, D), r(1, 5, KV, D), r(1, 5, KV, D)
    tb = jnp.asarray([5, 6], jnp.int32)
    a = ops.paged_prefill_attention(qc, kp, vp, tb, 13, ks, vs, impl="jnp")
    b = ops.paged_prefill_attention(qc, kp, vp, tb, 13, ks, vs, impl="pallas")
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    assert err < 2e-2, f"paged prefill interpret mismatch: {err}"
    print("interpret-mode kernel validation OK")


def bench_config():
    """The model every grid point runs: tests/test_calibration.py rebuilds
    predictions from the artifact with exactly this config."""
    return get_reduced_config("yi-6b", num_layers=2)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small grid + interpret kernel validation (CI)")
    ap.add_argument("--out", default=os.path.join(ARTIFACTS,
                                                  "kernel_calibration.json"))
    args = ap.parse_args(argv)

    if args.quick:
        validate_kernels_interpret()
        batches, ctxs = [1, 8], [64, 128]
        chunks = [(16, 0), (16, 64), (32, 64)]
    else:
        batches, ctxs = [1, 2, 4, 8, 16], [128, 256]
        chunks = [(16, 0), (32, 0), (64, 0), (32, 128), (64, 128), (64, 256)]

    cfg = bench_config()
    host = measure_host_chip(quick=args.quick)
    print(f"host: {host['backend']} peak={host['peak_flops']/1e9:.1f} GFLOP/s "
          f"bw={host['bandwidth']/1e9:.1f} GB/s")

    decode_rows = [time_decode_step(cfg, b, c) for b in batches for c in ctxs]
    for r in decode_rows:
        print(f"decode b={r['batch']:3d} ctx={r['ctx']:4d} "
              f"paged={r['paged_s']*1e3:7.2f}ms dense={r['dense_s']*1e3:7.2f}ms "
              f"speedup={r['speedup']:.2f}x")
    prefill_rows = [time_prefill_chunk(cfg, ch, cx) for ch, cx in chunks]
    for r in prefill_rows:
        print(f"prefill chunk={r['chunk']:4d} ctx0={r['ctx0']:4d} "
              f"paged={r['paged_s']*1e3:7.2f}ms")

    calib = fit_calibration(cfg, host, decode_rows, prefill_rows)
    preds = predict(cfg, host, calib, decode_rows, prefill_rows)
    rel = [abs(p["predicted_s"] - p["measured_s"]) / max(p["measured_s"], 1e-12)
           for p in preds]
    tolerance = float(min(max(1.5 * max(rel), 0.25), 2.0))
    print(f"calibration: {calib}")
    print(f"max rel err {max(rel):.3f} -> tolerance {tolerance:.3f}")

    big = [r for r in decode_rows if r["batch"] >= 8]
    if big:
        worst = min(r["speedup"] for r in big)
        print(f"paged-vs-dense at batch>=8: worst speedup {worst:.2f}x")
        if not args.quick:
            assert worst > 1.0, \
                f"paged decode must beat dense gather at batch >= 8 ({worst:.2f}x)"

    art = {
        "config": {"arch": "yi-6b-reduced", "num_layers": cfg.num_layers,
                   "block_size": BLOCK_SIZE},
        "host": host,
        "calibration": calib,
        "decode": decode_rows,
        "prefill": prefill_rows,
        "predictions": preds,
        "tolerance": tolerance,
    }
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
    print(f"wrote {args.out}")
    return art


if __name__ == "__main__":
    main()
