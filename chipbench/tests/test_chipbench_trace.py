"""The trace reduction, against a small cut of a recorded chip trace."""
import json
import os

import pytest

from chipbench.lib import trace

CUT = os.path.join(os.path.dirname(__file__), "data", "trace_cut.json")


@pytest.fixture(scope="module")
def data():
    with open(CUT) as f:
        return json.load(f)


def device_line(data, name):
    plane = [p for p in data["planes"] if p["name"] == "/device:TPU:0"][0]
    return [e for ln in plane["lines"] if ln["name"] == name for e in ln["events"]]


def test_busy_is_the_union_of_op_intervals(data):
    r = trace.reduce(data)
    ops = sorted((s, s + d) for _, s, d in device_line(data, "XLA Ops"))
    merged = []
    for s, e in ops:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    assert r.busy_s == pytest.approx(sum(e - s for s, e in merged) / 1e9)
    assert r.window_s == pytest.approx(0.082050441)
    assert 0 < r.busy_s < r.window_s and r.chips == 1
    # nested ops count once: self times add up to the busy time
    assert sum(r.op_s.values()) == pytest.approx(r.busy_s, rel=1e-9)
    # the idle gaps fill the rest of the window
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s, abs=2e-5)


def test_kernel_and_program_times(data):
    r = trace.reduce(data)
    dec = [d for n, _, d in device_line(data, "XLA Ops") if n.startswith("%paged_decode_attention")]
    assert r.op_time("^paged_decode_attention") == (pytest.approx(sum(dec) / 1e9), 32)
    pre = [d for n, _, d in device_line(data, "XLA Ops") if n.startswith("%paged_prefill_attention")]
    assert r.op_time("^paged_prefill_attention") == (pytest.approx(sum(pre) / 1e9), 32)
    mods = {n: d for n, _, d in device_line(data, "XLA Modules")}
    step = [d for n, d in mods.items() if "_serve_step_paged" in n]
    assert r.module_time("_serve_step_paged") == (pytest.approx(step[0] / 1e9), 1)
    assert r.module_time("_prefill_chunk_paged")[1] == 1


def test_breakdown(data):
    b = trace.reduce(data).breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert "paged_decode_attention_grouped" in names and "copy" in names
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_op_names_and_union():
    assert trace.op_name("%fusion.12 = bf16[4]{0} fusion(x)") == "fusion"
    assert trace.op_name("%paged_decode_attention_grouped.3") == "paged_decode_attention_grouped"
    assert trace.op_name("%copy-start.2 = (s32[6]) copy-start(y)") == "copy-start"
    assert trace.union_ns([(0, 10), (5, 12), (20, 30)]) == 22
    nested = trace.self_times([("a", 0, 10), ("b", 2, 3), ("c", 6, 2)])
    assert [(n, own) for n, _, _, own in nested] == [("a", 5), ("b", 3), ("c", 2)]


def test_no_device_ops_reads_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["chipbench.window", 0, 100]]}]}]}
    assert trace.reduce(host_only) is None
