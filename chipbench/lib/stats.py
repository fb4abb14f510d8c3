"""Latency and rate arithmetic over a run's per-token record.

A run records, for each request, when it fell due and the wall time of
every token it emitted. The end-to-end metrics reduce that record over a
measured window [t0, t1): every request due in it, every gap that closes
in it, every token emitted in it. Nothing is taken from medians of pieces.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import numpy as np


@dataclasses.dataclass
class Track:
    """One request as the client saw it."""
    due: float                                   # when it fell due (wall, s)
    stream: int                                  # which stream it came from
    token_times: list = dataclasses.field(default_factory=list)
    submitted: Optional[float] = None            # when it was handed to the engine
    max_new: int = 0
    prompt_len: int = 0


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks, as
    numpy's default; NaN for no values."""
    if len(values) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(tracks: Sequence[Track], t0: float, t1: float, stop: float) -> list[float]:
    """Time to first token of every request due in [t0, t1). A request
    with no first token by `stop` counts what it waited so far, `stop`
    minus its due time, which puts it at the tail."""
    out = []
    for tr in tracks:
        if t0 <= tr.due < t1:
            first = tr.token_times[0] if tr.token_times else stop
            out.append(first - tr.due)
    return out


def gaps(tracks: Sequence[Track], t0: float, t1: float) -> list[float]:
    """Every inter-token gap, over all requests, that closes in [t0, t1)."""
    out = []
    for tr in tracks:
        tt = tr.token_times
        for a, b in zip(tt, tt[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def tokens_in(tracks: Sequence[Track], t0: float, t1: float) -> int:
    """Output tokens emitted in [t0, t1), over all requests."""
    return sum(1 for tr in tracks for t in tr.token_times if t0 <= t < t1)


def attainment(tracks: Sequence[Track], t0: float, t1: float, stop: float,
               ttft_s: float, gap_s: float) -> float:
    """Share of the requests due in [t0, t1) whose first token came within
    `ttft_s` and whose every gap stayed within `gap_s`; one with no first
    token by `stop` misses."""
    due = [tr for tr in tracks if t0 <= tr.due < t1]
    if not due:
        return float("nan")
    met = 0
    for tr in due:
        tt = tr.token_times
        if not tt or tt[0] - tr.due > ttft_s:
            continue
        if all(b - a <= gap_s for a, b in zip(tt, tt[1:])):
            met += 1
    return met / len(due)


def quartile_spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, with the quartiles of Python's
    `statistics.quantiles(values, n=4)`."""
    import statistics
    q1, q2, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / q2 if q2 else math.inf
