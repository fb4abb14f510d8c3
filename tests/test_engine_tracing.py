"""The serving engine's own measurement: its counters (queue wait,
preemptions), its host spans on the profiler's clock, and the names of
the programs a device trace finds the step and the KV write by.

Tiny config on the CPU; the spans and counters must not change a token.
"""
import glob
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import backbone
from repro.models.layers import DEFAULT_EXEC
from repro.serving.batching import BatchPolicy
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import _append_slots

CFG = get_reduced_config("yi-6b", num_layers=2)
PHASES = ("engine.step", "engine.schedule", "engine.prefill", "engine.decode",
          "engine.sample", "engine.emit")


@pytest.fixture(scope="module")
def params():
    return backbone.init_params(jax.random.PRNGKey(0), CFG)


def _engine(params, pool_blocks, paged=False):
    policy = BatchPolicy(kind="continuous", chunk_tokens=16, block_size=8,
                         num_blocks=pool_blocks)
    return ServingEngine(CFG, params, kind="standalone", temperature=0.0, seed=0,
                         block_size=8, pool_blocks=pool_blocks, batching=policy,
                         paged=paged)


def _serve(eng, n=5, prompt=20, new=24):
    """Submit `n` requests and run to idle; returns the plans the
    scheduler handed out, in order."""
    plans = []
    next_plan = eng._sched.next_plan

    def recording():
        plan = next_plan()
        if plan is not None:
            plans.append(plan)
        return plan

    eng._sched.next_plan = recording
    rng = np.random.default_rng(7)
    for _ in range(n):
        eng.submit(rng.integers(1, 400, size=prompt), new)
    eng.run_until_idle()
    return plans


def test_preemptions_are_counted_per_plan(params):
    # 5 sequences of 20 + 24 tokens want 30 blocks of 8; the pool has 16
    eng = _engine(params, pool_blocks=16)
    plans = _serve(eng)
    preempted = sum(len(p.preempted) for p in plans)
    assert preempted > 0, "the pool was sized to force preemption"
    assert eng.counters.preempted == preempted
    assert len(eng.finished) == 5


def test_each_request_is_queued_once(params):
    eng = _engine(params, pool_blocks=16)
    plans = _serve(eng)
    victims = {v.sid for p in plans for v in p.preempted}
    # a victim prefills again from ctx 0: two plans hold its first chunk
    starts = Counter(ch.seq.sid for p in plans for ch in p.chunks if ch.ctx_before == 0)
    assert victims and all(starts[sid] >= 2 for sid in victims)
    assert eng.counters.queued == 5
    waits = [r.queue_wait_s for r in eng.finished]
    assert all(w is not None and w >= 0 for w in waits)
    assert eng.counters.queue_wait_s == pytest.approx(sum(waits))


def _xplane_lines(directory):
    path = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return {(plane.name, line.name): {ev.name for ev in line.events}
            for plane in pd.planes for line in plane.lines}


def test_spans_change_no_token_and_land_on_one_thread(params, tmp_path):
    plain = _engine(params, pool_blocks=64, paged=True)
    _serve(plain, n=3, new=6)
    traced = _engine(params, pool_blocks=64, paged=True)
    with jax.profiler.trace(str(tmp_path)):
        _serve(traced, n=3, new=6)
    tokens = lambda eng: {r.req_id: r.out_tokens for r in eng.finished}
    assert tokens(traced) == tokens(plain)
    assert plain.clock == traced.clock
    lines = _xplane_lines(str(tmp_path))
    assert any(set(PHASES) <= names for names in lines.values()), \
        "every engine span on one host thread"


def test_step_and_kv_write_programs_keep_their_names(params):
    """A device trace names each program run `jit_<function>(<fp>)` on its
    `XLA Modules` line; the benchmark finds the step programs and the KV
    write by these names."""
    pool = jnp.zeros((CFG.num_layers, 5, CFG.attn.num_kv_heads, 8, CFG.attn.head_dim),
                     jnp.dtype(CFG.dtype))
    tables = jnp.zeros((2, 2), jnp.int32)
    lengths = jnp.array([3, 5], jnp.int32)
    tokens = jnp.array([1, 2], jnp.int32)
    assert backbone._serve_step_paged.lower(
        params, pool, pool, tables, lengths, tokens, cfg=CFG, exec_cfg=DEFAULT_EXEC,
        max_len=6, impl="jnp").as_text().startswith("module @jit__serve_step_paged")
    # the chip runs the traced-ctx jit of the same function (the jnp twin
    # needs a static ctx0 to lower here)
    assert backbone._prefill_chunk_traced_ctx.__wrapped__ is backbone._prefill_chunk_paged
    assert backbone._prefill_chunk_static_ctx.lower(
        params, pool, pool, tables[0], 8, jnp.arange(4, dtype=jnp.int32), cfg=CFG,
        exec_cfg=DEFAULT_EXEC, impl="jnp").as_text().startswith("module @jit__prefill_chunk_paged")
    kv = jnp.zeros((CFG.num_layers, 2, CFG.attn.num_kv_heads, CFG.attn.head_dim), pool.dtype)
    text = _append_slots.lower(pool, pool, kv, kv, jnp.array([0, 1]),
                               jnp.array([2, 3])).as_text()
    assert text.startswith("module @jit__append_slots_impl")
