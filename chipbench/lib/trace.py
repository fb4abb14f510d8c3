"""Reduction of a profiler trace to busy time, op and kernel times, and
idle gaps labelled by what the host was doing.

The profiler writes an `.xplane.pb`; `load` turns it into plain data
(planes, their lines, events as (name, start_ns, duration_ns)), which is
also the form of the small recorded trace the tests check this against.
Device planes are those named `/device:TPU:<n>`; their `XLA Ops` line
holds one event per operation run, named by its HLO instruction
(`%paged_decode_attention_grouped.3 = bf16[...] custom-call(...)`), with
a loop's body ops nested inside the loop's own event; their `XLA
Modules` line holds one event per program run (`jit__serve_step_paged(
<fingerprint>)`). The benchmark's own host span `chipbench.window`, on the
main Python thread, marks the measured window on the same clock; idle
gaps are labelled by what that thread was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"
# host spans too generic to say what the host was doing
_GENERIC = re.compile(r"^(chipbench\.window|\$.*)$")
_HLO_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(\.\d+)?( = .*)?$", re.S)


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[...] ...` -> `fusion`: the instruction's name
    without its number, so the calls of one op add up."""
    m = _HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def self_times(events) -> list:
    """(name, start, end, self_ns) of nested events on one line: each
    event's duration less that of the events directly inside it."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, s + d, d]
        if stack:
            stack[-1][3] -= d
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def load(path: str) -> dict:
    """Plain data of an `.xplane.pb` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                                     for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def union_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # union of device op intervals, mean over chips
    op_s: dict                        # op name -> device seconds (all chips)
    op_n: dict                        # op name -> count
    module_s: dict                    # program name -> device seconds
    module_n: dict
    gaps: list                        # (label, seconds) of each idle gap
    chips: int

    def op_time(self, pattern: str) -> tuple[float, int]:
        """(device seconds, calls) of the ops whose name matches `pattern`."""
        rx = re.compile(pattern)
        hit = [k for k in self.op_s if rx.search(k)]
        return sum(self.op_s[k] for k in hit), sum(self.op_n[k] for k in hit)

    def module_time(self, pattern: str) -> tuple[float, int]:
        rx = re.compile(pattern)
        hit = [k for k in self.module_s if rx.search(k)]
        return sum(self.module_s[k] for k in hit), sum(self.module_n[k] for k in hit)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        by_label = defaultdict(float)
        for label, s in self.gaps:
            by_label[label] += s
        idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(data: dict) -> Optional[Reduced]:
    """Reduce plain trace data over the `chipbench.window` span (or the
    whole trace where the span is missing). None where no device plane
    holds an op."""
    window, main = None, []
    for plane in data["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == WINDOW_SPAN:
                    window, main = (s, s + d), line["events"]
    inside = (lambda s, d: True) if window is None else \
        (lambda s, d: window[0] <= s and s + d <= window[1])
    op_s, op_n = defaultdict(float), defaultdict(int)
    mod_s, mod_n = defaultdict(float), defaultdict(int)
    busy, first = [], None
    for plane in data["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        intervals = []
        for ln in plane["lines"]:
            events = [e for e in ln["events"] if inside(e[1], e[2])]
            if ln["name"] == OPS_LINE:
                for name, s, e, own in self_times(events):
                    key = op_name(name)
                    op_s[key] += own / 1e9
                    op_n[key] += 1
                    intervals.append((s, e))
            elif ln["name"] == MODULES_LINE:
                for name, s, d in events:
                    mod_s[name] += d / 1e9
                    mod_n[name] += 1
        if intervals:
            busy.append(union_ns(intervals))
            if first is None:
                first = sorted(intervals)
    if first is None:
        return None
    if window is None:
        window = (first[0][0], max(e for _, e in first))
    gaps = _gaps(first, window, main)
    return Reduced(window_s=(window[1] - window[0]) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9, op_s=dict(op_s), op_n=dict(op_n),
                   module_s=dict(mod_s), module_n=dict(mod_n), gaps=gaps, chips=len(busy))


def _gaps(ops, window, host_events, min_ns: int = 1000):
    """Idle gaps on the first chip, each labelled by the innermost host
    event that covers its middle."""
    merged = []
    for s, e in ops:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    edges = [window[0]] + [x for se in merged for x in se] + [window[1]]
    host = sorted((s, s + d, name) for name, s, d in host_events if not _GENERIC.match(name))
    spans = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b - a >= min_ns]
    out, active, i = [], [], 0
    for a, b in spans:                      # in time order
        mid = (a + b) // 2
        while i < len(host) and host[i][0] <= mid:
            active.append(host[i])
            i += 1
        active = [ev for ev in active if ev[1] >= mid]
        best = min(active, key=lambda ev: ev[1] - ev[0], default=None)
        out.append((best[2] if best else "host: none traced", (b - a) / 1e9))
    return out


def save(data: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(data, f)
