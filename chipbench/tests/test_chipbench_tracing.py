"""The KV write's share of the device's busy time, read off a recorded
chip trace, and a traced run on the CPU, where no device-trace metric
may be reported."""
import json
import os
import types

import pytest

from chipbench.lib import registry, trace
from chipbench.lib.harness import run_cell
from chipbench.tests.test_chipbench_run import CELLS, SEED, tiny

DATA = os.path.join(os.path.dirname(__file__), "data")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_kv_write_share_of_busy_time():
    data = load("trace_cut.json")
    plane = [p for p in data["planes"] if p["name"] == "/device:TPU:0"][0]
    mods = [e for ln in plane["lines"] if ln["name"] == "XLA Modules" for e in ln["events"]]
    kv = [d for n, _, d in mods if n.startswith("jit__append_slots_impl(")]
    assert kv
    r = trace.reduce(data)
    got = registry.metric_reducer("kv_write_busy_pct")(types.SimpleNamespace(trace=r))
    assert got == pytest.approx(100.0 * sum(kv) / 1e9 / r.busy_s)
    assert 0 < got < 100


def test_kv_write_share_reads_nothing_without_its_program():
    reducer = registry.metric_reducer("kv_write_busy_pct")
    assert reducer(types.SimpleNamespace(trace=None)) is None
    data = load("trace_cut.json")
    for plane in data["planes"]:
        for ln in plane["lines"]:
            ln["events"] = [e for e in ln["events"] if "_append_slots" not in e[0]]
    assert reducer(types.SimpleNamespace(trace=trace.reduce(data))) is None


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_on_the_cpu_reports_no_device_metric(workload):
    result, _ = run_cell(workload, SEED, 2.0, True, overrides=tiny(workload), chip_check=False)
    assert result["correct"] is True and "breakdown" not in result
    bench = registry.benchmark()
    device = {m["name"] for m in registry.cell_metrics(bench, workload, "per_layer")
              if m["source"] == "device_trace"}
    assert "kv_write_busy_pct" in device
    assert not device & set(result["metrics"])
    assert result["metrics"]["compiles_in_window"]["value"] >= 0


def test_engine_spans_share_the_device_clock():
    """A cut of a traced `yi6b.steady` window on one v5e (three engine
    steps): each decode program runs after its `engine.decode` span
    starts and ends before the next `engine.sample` span ends, so the
    engine's host spans and the device's programs are on one clock."""
    data = load("engine_spans_cut.json")
    steps = [(s, s + d) for p in data["planes"] if trace.DEVICE_PLANE.match(p["name"])
             for ln in p["lines"] if ln["name"] == trace.MODULES_LINE
             for n, s, d in ln["events"] if n.startswith("jit__serve_step_paged(")]
    host = [e for p in data["planes"] if not trace.DEVICE_PLANE.match(p["name"])
            for ln in p["lines"] for e in ln["events"]]
    decode = sorted((s, s + d) for n, s, d in host if n == "engine.decode")
    sample = sorted((s, s + d) for n, s, d in host if n == "engine.sample")
    assert len(steps) == len(decode) == 3
    for start, end in steps:
        span = max(d for d in decode if d[0] <= start)
        sync = min(x for x in sample if x[0] >= span[1])
        assert span[0] <= start and end <= sync[1]
