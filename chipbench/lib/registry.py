"""Finds a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each is a file of its
own (`configs/<config>.json`, `traffic/<traffic>.json`), and each
per-layer metric is a reducer of its own (`metrics/<metric>.py`). A later
change adds a cell, a configuration, a mix or a metric by adding files
and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def limits(workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """The limits of the numbers a cell's `correct` compares."""
    return load_json(os.path.join(bench_dir, "limits", f"{workload}.json"))


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise LookupError(f"device kind {device_kind!r} is not in peaks.json "
                          f"(known: {sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


def metric_reducer(name: str, bench_dir: str = BENCH_DIR):
    """The `reduce(ctx)` function of `metrics/<name>.py`."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The metrics of `kind` ("end_to_end" or "per_layer") that a cell
    reports: those that list it; one that lists no cells is reported in
    every cell, or, for a per-layer metric, in every cell that reports the
    end-to-end metric it moves."""
    out = []
    for m in bench[kind]:
        cells: Optional[list] = m.get("workloads")
        if cells is not None:
            hit = workload in cells
        elif kind == "per_layer":
            hit = any(e["name"] == m["moves"]
                      for e in cell_metrics(bench, workload, "end_to_end"))
        else:
            hit = True
        if hit:
            out.append(m)
    return out
