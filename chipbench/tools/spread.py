"""Spreads of the end-to-end metrics over sets of runs (`repeat.py` output).

    python3 chipbench/tools/spread.py chiprun_out/steady_a.jsonl chiprun_out/steady_b.jsonl

Each file is one set. For each cell and metric it prints each set's
median and its spread, (Q3 - Q1) / median with the quartiles of
`statistics.quantiles(values, n=4)`, the spread with each set's run
farthest from its median left out, and five times the wider spread (the
bound that spread would give).
"""
from __future__ import annotations

import json
import os
import statistics
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from chipbench.lib.stats import quartile_spread as spread  # noqa: E402


def trimmed(values) -> list:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return [v for i, v in enumerate(values) if i != far]


def main(paths) -> None:
    sets = []
    for path in paths:
        by = defaultdict(lambda: defaultdict(list))
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                res = rec["result"]
                if not res:
                    continue
                for name, m in res["metrics"].items():
                    by[rec["workload"]][name].append(m["value"])
                by[rec["workload"]]["correct"].append(float(res["correct"]))
        sets.append(by)
    cells = sorted({c for s in sets for c in s})
    for c in cells:
        for name in sorted({n for s in sets for n in s.get(c, {})}):
            row = []
            for s in sets:
                v = s.get(c, {}).get(name, [])
                if len(v) >= 3 and name != "correct":
                    row.append((statistics.median(v), spread(v), spread(trimmed(v))
                                if len(v) >= 4 else float("nan"), len(v)))
                else:
                    row.append((statistics.median(v) if v else float("nan"), float("nan"),
                                float("nan"), len(v)))
            widest = max((r[1] for r in row if r[1] == r[1]), default=float("nan"))
            cols = "  ".join(f"med {m:.6g} spread {sp:.4f} trim {tr:.4f} n {n}"
                             for m, sp, tr, n in row)
            print(f"{c:22} {name:22} {cols}  5x {5 * widest:.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
