"""One run of one cell: set-up, warm-up, the measured window, the check.

`run_cell` is the whole run that `chipbench/run.py` makes; the tests call
it too, at a tiny size on the CPU, with `require_chip=False`.

The run, in one process:
1. looks for the chip: a device kind missing from `peaks.json`, or fewer
   chips than the cell asks for, ends the run before anything is built;
2. keeps JAX's persistent compilation cache in the checkout and persists
   every program, so later runs of a checkout load what its first run
   compiled;
3. makes the weights on the device from the seed (`weights.py`) and builds
   the program's `ServingEngine` with the configuration's knobs;
4. warms up by serving the cell's own traffic, drawn from a stream of
   the seed other than the measured one, and switches to the measured
   stream without draining;
5. drives `ServingEngine.step()` in wall time for the window (`loop.py`);
   with `trace` on, under the profiler and with the step programs'
   shapes recorded;
6. checks the served tokens of a sample of the finished requests against
   the plain reference (`check.py`), once the engine is freed. With
   `control` on, the tokens that the reference in fp8 puts first stand in
   the served tokens' place, and `correct` judges them.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Callable, Optional

import numpy as np

from chipbench.lib import registry
from chipbench.lib import stats
from chipbench.lib.trace import WINDOW_SPAN
from chipbench.lib.traffic import (MEASURED, WARMUP, draw_population, requests,
                                   staggered, system_prompts)

CACHE_DIR = os.path.join(registry.ROOT, ".jax_cache")
TRACE_DIR = os.path.join(registry.ROOT, ".chipbench_trace")


class NoChip(SystemExit):
    """The machine lacks the chip the cell asks for: no result is printed."""


def log(*a) -> None:
    print(*a, flush=True)


def _program():
    """The program's modules, imported once JAX's cache is configured."""
    src = os.path.join(registry.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs import get_config
    from repro.models import backbone
    from repro.serving.batching import BatchPolicy
    from repro.serving.engine import ServingEngine
    return get_config, backbone, BatchPolicy, ServingEngine


def program_config(conf: dict, get_config):
    """The program's ModelConfig for a configuration file: the registry
    arch with the file's numbers applied, so the file is what runs."""
    m = conf["model"]
    base = get_config(conf["program_arch"])
    attn = dataclasses.replace(
        base.attn, num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        rope_theta=float(m["rope_theta"]))
    return dataclasses.replace(
        base, name=conf["name"], num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], attn=attn, norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), dtype=m["torch_dtype"])


def require_chip(jax, chips: int):
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: needs a TPU; JAX found {devs[0].platform} ({kind})")
    try:
        registry.peaks(kind)
    except LookupError as e:
        raise NoChip(f"chipbench: {e}")
    if len(devs) < chips:
        raise NoChip(f"chipbench: the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs


class CallLog:
    """Shapes of the program's step calls during a traced window, taken
    by wrapping the backbone's two entry points the engine calls."""

    def __init__(self, backbone):
        self.backbone = backbone
        self.decodes: list[list[int]] = []       # cached lengths per decode call
        self.chunks: list[tuple[int, int]] = []  # (tokens, ctx0) per chunk call
        self._orig = (backbone.serve_step_paged, backbone.prefill_chunk_paged)

    def __enter__(self):
        dec, chk = self._orig

        def serve_step_paged(params, pages_k, pages_v, tables, lengths, tokens, *a, **k):
            self.decodes.append([int(x) for x in np.asarray(lengths)])
            return dec(params, pages_k, pages_v, tables, lengths, tokens, *a, **k)

        def prefill_chunk_paged(params, pages_k, pages_v, table, ctx0, tokens, *a, **k):
            self.chunks.append((int(tokens.shape[0]), int(ctx0)))
            return chk(params, pages_k, pages_v, table, ctx0, tokens, *a, **k)

        self.backbone.serve_step_paged = serve_step_paged
        self.backbone.prefill_chunk_paged = prefill_chunk_paged
        return self

    def __exit__(self, *exc):
        self.backbone.serve_step_paged, self.backbone.prefill_chunk_paged = self._orig


@dataclasses.dataclass
class RunContext:
    """What a metric's reducer reads (`metrics/<name>.py`)."""
    workload: str
    model: dict
    peaks: Optional[dict]
    t0: float
    t1: float
    stop: float
    setup_s: float
    tracks: list
    steps: list
    pool_blocks: int
    compiles: int = 0
    compile_s: float = 0.0
    prefix_hit_tokens: Optional[int] = None
    prompt_tokens: int = 0
    calls: Optional[CallLog] = None
    trace: Optional[object] = None       # trace.Reduced, traced runs only

    def window_steps(self):
        return [s for s in self.steps if self.t0 <= s.t_start and s.t_end <= self.t1]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Optional[dict] = None, overrides: Optional[dict] = None,
             chip_check: bool = True, engine_hook: Optional[Callable] = None,
             t_process: Optional[float] = None,
             keep_trace: Optional[str] = None, control: bool = False) -> tuple[dict, list]:
    """One run. Returns (result, checks): the result line's object and the
    numbers compared with their limits."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = bench or registry.benchmark()
    cell = registry.cell(bench, workload)
    conf = registry.config(bench, cell["config"])
    spec = registry.traffic(cell["traffic"])
    lim = registry.limits(workload)
    if overrides:
        conf = _merge(conf, overrides.get("config", {}))
        spec = _merge(spec, overrides.get("traffic", {}))
        lim = _merge(lim, overrides.get("limits", {}))
    model, eng_knobs = conf["model"], conf["engine"]

    if chip_check:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    if chip_check:
        devs = require_chip(jax, int(cell["chips"]))
    else:
        devs = jax.devices()
    dev = devs[0]
    peaks = registry.peaks(dev.device_kind) if chip_check else None

    get_config, backbone, BatchPolicy, ServingEngine = _program()
    from repro.launch.serve import enable_compile_cache
    from repro.models import init_params
    from chipbench.lib.compiles import CompileCounter
    from chipbench.lib.weights import check_layout, dims_of, make_weights, seed_key

    cache_dir = None
    if chip_check:
        cache_dir = enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devs)}")
    log(f"compile cache: {cache_dir}")
    counter = CompileCounter()
    try:
        cfg = program_config(conf, get_config)
        key = seed_key(seed)
        check_layout(jax.eval_shape(make_weights, key, dims_of(model)),
                     jax.eval_shape(init_params, key, cfg))
        weights = make_weights(key, dims_of(model))
        jax.block_until_ready(weights)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(weights))
        log(f"model: {conf['name']} layers={model['num_hidden_layers']} "
            f"d_model={model['hidden_size']} weights {nbytes / 1e9:.3f} GB, made from the seed "
            f"({time.perf_counter() - t_process:.3f} s since start)")

        policy = BatchPolicy(kind="continuous", chunk_tokens=eng_knobs["chunk_tokens"],
                             token_budget=eng_knobs["token_budget"],
                             block_size=eng_knobs["block_size"],
                             num_blocks=eng_knobs["pool_blocks"],
                             prefix_cache=bool(eng_knobs["prefix_cache"]))
        engine = ServingEngine(cfg, weights, kind="standalone", new_chip="tpu_v5e",
                               max_batch=eng_knobs["max_batch"],
                               pool_blocks=eng_knobs["pool_blocks"],
                               block_size=eng_knobs["block_size"], temperature=0.0,
                               seed=int(seed) & 0x7FFFFFFF, batching=policy)
        if engine_hook is not None:
            engine_hook(engine)
        log(f"engine: paged={engine.paged} max_batch={engine.max_batch} "
            f"pool_blocks={engine.pool.num_blocks} block_size={engine.pool.block_size} "
            f"chunk_tokens={policy.chunk_tokens} token_budget={policy.token_budget} "
            f"prefix_cache={policy.prefix_cache}")

        from chipbench.lib.loop import Driver
        drv = Driver(engine)
        pop = draw_population(spec)
        vocab = model["vocab_size"]
        prefixes = system_prompts(spec, seed, vocab)
        warm = requests(spec, pop, seed, WARMUP, vocab, prefixes)
        meas = requests(spec, pop, seed, MEASURED, vocab, prefixes)
        closed = spec["loop"] == "closed"
        conc = int(spec.get("concurrency") or
                   engine.max_batch + int(spec.get("outstanding_over_max_batch", 0)))

        # ---- warm-up: the cell's own traffic, another stream of the seed.
        # A closed loop starts as if it had run for a while (`staggered`)
        # and runs the mix's `warmup_steps`, which meet every program shape
        # of its steady state.
        tw = time.perf_counter()
        c_w = counter.snapshot()
        if closed:
            n0 = len(drv.steps)
            drv.run_closed(staggered(warm, conc, seed, vocab, spec["population_seed"],
                                     eng_knobs["block_size"]), conc,
                           tw + spec["warmup_max_s"], WARMUP,
                           done=lambda: len(drv.steps) - n0 >= spec["warmup_steps"])
        else:
            drv.run_open(warm, tw, tw + spec["warmup_s"], WARMUP)
        c_w1 = counter.snapshot()
        log(f"warm-up: {time.perf_counter() - tw:.3f} s, {len(drv.steps)} steps, "
            f"{len(drv.finished)} requests finished; {c_w1[0] - c_w[0]} programs "
            f"({c_w1[1] - c_w[1]:.1f} s), {c_w1[2] - c_w[2]} of them from the persistent cache")

        # ---- the window: no persistent cache inside it, so that what it
        # compiles costs every run the same
        if cache_dir:
            _persistent_cache(jax, False)
        cache = engine._sched.cache if engine._sched is not None else None
        hits0 = cache.stats()["hit_tokens"] if cache is not None else None
        calls = CallLog(backbone) if trace else None
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            _start_profiler(jax)
            calls.__enter__()
            window_span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            window_span.__enter__()
        gc_log = GcLog()
        c0 = counter.snapshot()
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        t1 = t0 + seconds
        n_tracks0 = len(drv.tracks)
        if closed:
            drv.run_closed(meas, conc, t1, MEASURED)
            pend = None
        else:
            pend = drv.run_open(meas, t0, t1, MEASURED)
        jax.block_until_ready((engine.pool.k, engine.pool.v))
        t_closed = time.perf_counter()
        c1 = counter.snapshot()
        gc_log.close()
        if trace:
            window_span.__exit__(None, None, None)
            calls.__exit__()
            jax.profiler.stop_trace()
        hits1 = cache.stats()["hit_tokens"] if cache is not None else None
        prompt_tokens = sum(tr.prompt_len for tr in drv.tracks[n_tracks0:]
                            if tr.submitted is not None and tr.submitted < t1)

        # ---- every request due in the window gets its chance at a first
        # token, with the load kept on
        limit = t1 + spec.get("drain_s", 30.0)

        def waiting():
            return (pend is not None and t0 + pend.due_s < t1) or any(
                tr.stream == MEASURED and t0 <= tr.due < t1 and not tr.token_times
                for tr in drv.tracks)

        while waiting() and time.perf_counter() < limit:
            until = min(time.perf_counter() + 0.25, limit)
            if closed:
                drv.run_closed(meas, conc, until, MEASURED)
            else:
                pend = drv.run_open(meas, t0, until, MEASURED, pending=pend)
        stop = time.perf_counter()
        if cache_dir:
            _persistent_cache(jax, True)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

        ctx = RunContext(workload=workload, model=model, peaks=peaks, t0=t0, t1=t1,
                         stop=stop, setup_s=setup_s, tracks=drv.tracks, steps=drv.steps,
                         pool_blocks=engine.pool.num_blocks,
                         compiles=(c1[0] - c0[0]) - (c1[2] - c0[2]), compile_s=c1[1] - c0[1],
                         prefix_hit_tokens=None if hits0 is None else hits1 - hits0,
                         prompt_tokens=prompt_tokens, calls=calls)
        _report_window(ctx, drv, spec, t_closed, gc_log)

        # ---- free the engine, then check against the reference
        finished = [r for r, tr in drv.finished
                    if r.status == "ok" and tr.token_times and t0 <= tr.token_times[-1]]
        attempted = [tr for tr in drv.tracks if tr.stream == MEASURED and t0 <= tr.due < t1]
        failed = sum(1 for r, tr in drv.finished + drv.live
                     if tr.stream == MEASURED and t0 <= tr.due < t1 and r.status != "ok")
        del engine, drv, cache      # the prefix cache holds the engine through its hooks
        gc.collect()
        checks, control_reading = _check(conf, lim, weights, finished, seed, control)
        del weights
        gc.collect()

        if trace:
            from chipbench.lib import trace as tr_mod
            path = tr_mod.find_xplane(TRACE_DIR)
            data = tr_mod.load(path) if path else None
            ctx.trace = tr_mod.reduce(data) if data else None
            if keep_trace and data:
                os.makedirs(keep_trace, exist_ok=True)
                tr_mod.save(data, os.path.join(keep_trace, f"{workload}-{seed}.json"))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        kind = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in registry.cell_metrics(bench, workload, kind):
            val = registry.metric_reducer(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
                  "memory_peak_bytes": peak}
        result = {"correct": all(c["value"] is not None and c["value"] <= c["limit"]
                                 for c in checks),
                  "attempted": len(attempted), "failed": failed,
                  "metrics": metrics, "device": device}
        if trace and ctx.trace is not None:
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            result["breakdown"] = ctx.trace.breakdown()
        if control:
            result["control"] = control_reading
        result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                            for c in checks}
        return result, checks
    finally:
        counter.close()


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def _persistent_cache(jax, on: bool) -> None:
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", on)
    compilation_cache.reset_cache()


def _start_profiler(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    return opts


class GcLog:
    """Python's garbage collections while it is open, and their pauses."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, seconds)
        self._start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._start))

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


def _report_window(ctx: RunContext, drv, spec: dict, t_closed: float, gc_log: GcLog) -> None:
    """Facts about the window, on lines before the result."""
    ws = ctx.window_steps()
    due = [tr for tr in ctx.tracks if tr.stream == MEASURED and ctx.t0 <= tr.due < ctx.t1]
    got_first = sum(1 for tr in due if tr.token_times)
    done = sum(1 for tr in due if len(tr.token_times) >= tr.max_new)
    log(f"window: {ctx.t1 - ctx.t0:.3f} s (closed {t_closed - ctx.t0:.3f} s after it began), "
        f"{len(ws)} steps, {stats.tokens_in(ctx.tracks, ctx.t0, ctx.t1)} tokens, "
        f"{len(due)} requests due, {got_first} had a first token and {done} finished "
        f"by {ctx.stop - ctx.t0:.3f} s; {ctx.compiles} compilations in the window "
        f"({ctx.compile_s:.3f} s)")
    if ws:
        t = np.array([x.t_end - ctx.t0 for x in ws])
        q = np.array([x.outstanding for x in ws], np.float64)
        slope = float(np.polyfit(t, q, 1)[0]) if len(ws) > 1 else 0.0
        log(f"backlog: {ws[0].outstanding} outstanding at the first step, {ws[-1].outstanding} "
            f"at the last, trend {slope:+.3f} requests/s")
        wall = np.mean([s.t_end - s.t_start for s in ws])
        model = np.mean([s.model_dt for s in ws])
        log(f"step time: measured mean {wall * 1e3:.3f} ms, perfmodel predicted "
            f"{model * 1e3:.3f} ms (engine.clock)")
        dur = np.array([x.t_end - x.t_start for x in ws])
        slow = dur > 1.5 * np.median(dur)
        log(f"slow steps: {int(slow.sum())} over 1.5 x the median step, "
            f"{float((dur[slow] - np.median(dur)).sum()) * 1e3:.3f} ms beyond it, the slowest "
            f"{float(dur.max()) * 1e3:.3f} ms at {ws[int(dur.argmax())].t_start - ctx.t0:.3f} s; "
            f"Python gc: {len(gc_log.pauses)} collections "
            f"({sum(1 for g, _ in gc_log.pauses if g == 2)} full), "
            f"{sum(d for _, d in gc_log.pauses) * 1e3:.3f} ms")
    late = drv.late
    if late:
        log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
            f"p99 {np.percentile(late, 99) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms "
            f"over {len(late)} hand-overs")
    lim = spec.get("limits") or {}
    if lim and due:
        att = stats.attainment(ctx.tracks, ctx.t0, ctx.t1, ctx.stop,
                               lim["ttft_ms"] / 1e3, lim["itl_ms"] / 1e3)
        log(f"attainment (printed, not judged): {att * 100:.1f}% of {len(due)} requests met "
            f"TTFT {lim['ttft_ms']} ms and every gap {lim['itl_ms']} ms")


def _check(conf: dict, lim: dict, weights, finished, seed: int, control: bool) -> list:
    from chipbench.lib import check
    ref = check.reference_module(conf["reference"])
    picked = check.sample(finished, seed, lim["min_tokens"], lim["max_requests"])
    if not picked:
        log("check: no finished request to compare")
        return [{"name": "served_gap", "value": None, "limit": lim["served_gap"]}], None
    t = time.perf_counter()
    res = check.served_gaps(ref, weights, conf["model"], picked,
                            quant="fp8" if control else "none")
    log(f"check: {res['requests_compared']} requests, {res['tokens_compared']} served tokens "
        f"against the reference in {time.perf_counter() - t:.3f} s; "
        f"{res['served_not_argmax']} not the reference's argmax")
    value = res["served_gap"]
    if control:
        # the control's tokens stand where the served ones go, and are
        # judged by the same limit
        log(f"control (fp8 reference): widest gap {res['control_gap']!r}, "
            f"{res['control_not_argmax']} positions not the reference's argmax; "
            f"served {res['served_gap']!r}")
        value = res["control_gap"]
    return [{"name": "served_gap", "value": value, "limit": lim["served_gap"]}], res
