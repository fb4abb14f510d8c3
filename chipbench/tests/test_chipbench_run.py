"""Whole runs at a tiny size on the CPU, past the harness's look for a
chip: the result line's shape, the comparison with the reference, and
that a token altered where it is produced makes `correct` false."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench.lib import check, registry
from chipbench.lib.harness import run_cell

SEED = 2**33 + 2024
TINY = {"config": {"model": {"hidden_size": 128, "intermediate_size": 256,
                             "num_attention_heads": 4, "num_key_value_heads": 2,
                             "head_dim": 32, "vocab_size": 512, "num_hidden_layers": 2},
                   "engine": {"max_batch": 4, "pool_blocks": 128, "chunk_tokens": 32,
                              "token_budget": 64}},
        "traffic": {"concurrency": 3, "warmup_steps": 12, "warmup_max_s": 30, "drain_s": 10,
                    "context_cap": 96,
                    "prompt": {"tokens": 8}, "output": {"tokens": 4},
                    "outstanding_over_max_batch": 2},
        "limits": {"min_tokens": 20, "served_gap": 0.5}}


def tiny(workload, **traffic):
    over = json.loads(json.dumps(TINY))
    over["traffic"].update(traffic)
    return over


CELLS = [w["name"] for w in registry.benchmark()["workloads"]]


@pytest.mark.parametrize("workload,traffic", [(w, {}) for w in CELLS] + [
    # a closed loop that keeps requests waiting beyond the batch
    ("yi6b.steady", {"concurrency": 0, "outstanding_over_max_batch": 2}),
    # a shared system prompt, as a mix file may state one (the prefix cache adopts it)
    ("yi6b.steady", {"shared_prefix": {"groups": 2, "tokens": 32}}),
    # an open loop at a fixed rate, as a mix file may state it
    ("yi6b.steady", {"loop": "open", "rate_per_s": 3.0, "warmup_s": 3})])
def test_whole_run_tiny(workload, traffic):
    result, checks = run_cell(workload, SEED, 3.0, False, overrides=tiny(workload, **traffic),
                              chip_check=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["correct"] is True, checks
    assert result["failed"] == 0 and result["attempted"] > 0
    bench = registry.benchmark()
    want = {m["name"] for m in registry.cell_metrics(bench, workload, "end_to_end")}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert checks[0]["name"] == "served_gap" and 0 <= checks[0]["value"] <= 0.5


def alter_one_token(engine):
    """The timed path broken underneath: the tokens of every third
    sampling call are changed where the engine produces them (often
    enough that the drawn sample holds some)."""
    sample = engine._sample
    calls = {"n": 0}

    def altered(logits):
        out = sample(logits)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % logits.shape[-1]
        return out

    engine._sample = altered


def test_altered_token_is_not_correct():
    result, checks = run_cell("yi6b.steady", SEED, 3.0, False, overrides=tiny("yi6b.steady"),
                              chip_check=False, engine_hook=alter_one_token)
    assert result["correct"] is False
    assert checks[0]["value"] > checks[0]["limit"]


def drop_kv_appends(engine):
    """The timed path broken underneath: a step leaves the KV pool as it
    was (new keys and values are never written, on the paged path or on
    the dense one that the CPU takes)."""
    engine.pool.scatter_append = lambda *a, **k: None
    engine.pool.scatter = lambda *a, **k: None


def test_unchanged_kv_state_is_not_correct():
    result, checks = run_cell("yi6b.steady", SEED, 3.0, False, overrides=tiny("yi6b.steady"),
                              chip_check=False, engine_hook=drop_kv_appends)
    assert result["correct"] is False
    assert checks[0]["value"] > checks[0]["limit"]


# The control at a size a CPU test holds: 12-token outputs, 60 served
# tokens compared. The program read 0 to 0.0207 and the fp8 control
# 0.0885 to 0.164 on four seeds at this size, so the test's limit is 0.05.
CONTROL_TINY = {"traffic": {"prompt": {"tokens": 24}, "output": {"tokens": 12},
                            "warmup_steps": 40},
                "limits": {"min_tokens": 60, "max_requests": 8, "served_gap": 0.05}}


def test_control_is_not_correct():
    """The reference in fp8, put where the served tokens go, fails the
    same comparison that the program passes."""
    over = tiny("yi6b.steady", **CONTROL_TINY["traffic"])
    over["limits"] = dict(CONTROL_TINY["limits"])
    result, checks = run_cell("yi6b.steady", SEED, 5.0, False, overrides=over,
                              chip_check=False, control=True)
    assert result["correct"] is False
    assert checks[0]["value"] == result["control"]["control_gap"] > checks[0]["limit"]
    assert result["control"]["served_gap"] <= checks[0]["limit"]


def test_control_reads_far_above_the_program():
    """The control, the reference in fp8, departs from the float32
    reference by far more than the served tokens do (at the tiny size)."""
    import jax
    from chipbench.lib.weights import dims_of, make_weights, seed_key
    conf = registry.config(registry.benchmark(), "yi-6b")
    model = dict(conf["model"], **TINY["config"]["model"])
    w = make_weights(seed_key(SEED), dims_of(model))
    ref = check.reference_module(conf["reference"])
    rng = np.random.default_rng(0)

    class Req:
        """A prompt and the float32 reference's own greedy continuation."""
        def __init__(self, n, new=40):
            self.prompt = rng.integers(0, 512, size=n).astype(np.int32)
            seq = list(self.prompt)
            for _ in range(new):
                rows = ref.logits_rows(w, model, np.asarray(seq, np.int32), [len(seq) - 1])
                seq.append(int(jax.numpy.argmax(rows[0])))
            self.out_tokens = seq[n:]

    reqs = [Req(n) for n in (8, 30, 60)]
    res = check.served_gaps(ref, w, model, reqs, quant="fp8")
    assert res["served_gap"] == 0.0 and res["tokens_compared"] == 120
    assert res["control_not_argmax"] > 0 and res["control_gap"] > 0.1


def test_cli_without_a_chip_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(registry.BENCH_DIR)
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "yi6b.steady",
                        "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
                       cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
    # a directory with only BENCHMARK.json and the benchmark's files
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(registry.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "yi6b.steady",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout
