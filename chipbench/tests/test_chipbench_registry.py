"""A cell, configuration, traffic mix or metric is found by its name
alone; BENCHMARK.json keeps to its contract; a device missing from the
table of peaks is refused."""
import json
import os
import re
import types

import pytest

from chipbench.lib import registry
from chipbench.lib.harness import NoChip, require_chip

BENCH = registry.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_files_added_alone_are_found(tmp_path):
    """A later change adds files and entries only: nothing in the harness
    names a cell, configuration, mix or metric."""
    bench_dir = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (bench_dir / sub).mkdir(parents=True)
    conf = registry.config(BENCH, "yi-6b")
    conf["name"] = "yi-6b-new"
    (bench_dir / "configs" / "yi-6b-new.json").write_text(json.dumps(conf))
    mix = dict(registry.traffic("steady"), rate_per_s=0.5)
    (bench_dir / "traffic" / "chat_slow.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "new_metric.py").write_text(
        "def reduce(ctx):\n    return ctx * 2\n")
    (bench_dir / "limits" / "yi6b-new.chat_slow.json").write_text('{"served_gap": 1.0}')
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "yi-6b-new", "source": "x", "reduced": [], "why": "x",
                             "file": "chipbench/configs/yi-6b-new.json"})
    bench["workloads"].append({"name": "yi6b-new.chat_slow", "config": "yi-6b-new",
                               "traffic": "chat_slow", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "scheduler",
                               "moves": "itl_p50_ms", "workloads": ["yi6b-new.chat_slow"]})
    cell = registry.cell(bench, "yi6b-new.chat_slow")
    assert registry.config(bench, cell["config"], root=str(tmp_path))["name"] == "yi-6b-new"
    assert registry.traffic(cell["traffic"], bench_dir=str(bench_dir))["rate_per_s"] == 0.5
    assert registry.limits(cell["name"], bench_dir=str(bench_dir))["served_gap"] == 1.0
    names = [m["name"] for m in registry.cell_metrics(bench, cell["name"], "per_layer")]
    assert names == ["new_metric"]
    assert registry.metric_reducer("new_metric", bench_dir=str(bench_dir))(21) == 42
    e2e = [m["name"] for m in registry.cell_metrics(bench, cell["name"], "end_to_end")]
    assert e2e == ["itl_p50_ms", "itl_p95_ms", "setup_s"]


def test_per_layer_metric_without_cells_follows_its_moved_metric():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "batch.cell", "config": "yi-6b", "traffic": "steady",
                               "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": ["batch.cell"]})
    bench["per_layer"] = [{"name": "x", "unit": "%", "better": "higher",
                           "source": "device_trace", "layer": "kernels",
                           "moves": "output_tok_s"}]
    assert [m["name"] for m in registry.cell_metrics(bench, "batch.cell", "per_layer")] == ["x"]
    assert registry.cell_metrics(bench, "yi6b.steady", "per_layer") == []


def fake_jax(platform, kind, n):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_device_missing_from_peaks_is_refused():
    with pytest.raises(LookupError):
        registry.peaks("TPU v99")
    with pytest.raises(NoChip):
        require_chip(fake_jax("tpu", "TPU v99", 1), 1)
    with pytest.raises(NoChip):
        require_chip(fake_jax("cpu", "cpu", 1), 1)
    with pytest.raises(NoChip):
        require_chip(fake_jax("tpu", "TPU v5 lite", 1), 4)
    assert len(require_chip(fake_jax("tpu", "TPU v5 lite", 1), 1)) == 1


def test_benchmark_file_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    allnames = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
                for x in BENCH[k]]
    assert len(allnames) == len(set(allnames))
    assert all(NAME.match(n) for n in allnames)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        conf = registry.config(BENCH, c["name"])
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert c["file"].startswith("chipbench/")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(registry.BENCH_DIR, "metrics", m["name"] + ".py"))
    for m in BENCH["end_to_end"]:
        assert os.path.exists(os.path.join(registry.BENCH_DIR, "metrics", m["name"] + ".py"))
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        registry.traffic(w["traffic"])
        assert "served_gap" in registry.limits(w["name"])
        reported = {m["name"] for m in registry.cell_metrics(BENCH, w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = registry.cell_metrics(BENCH, w["name"], "per_layer")
        assert layer and all(m["moves"] in reported for m in layer)
