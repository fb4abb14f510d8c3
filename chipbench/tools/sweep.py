"""Find a cell's knee: one run per offered load, in one process.

    python3 chipbench/tools/sweep.py --workload yi6b.steady --concurrency 16 32 48 \
        --seconds 30 --seed 1
    python3 chipbench/tools/sweep.py --workload <open-loop cell> --rates 0.5 1 2 \
        --seconds 40 --seed 1

For each load, one run of the cell with the mix's concurrency (closed
loop) or rate (open loop) replaced; each prints its window facts and its
result line. For a closed loop the knee is the highest concurrency whose
tails meet the mix's limits; for an open loop, the highest rate whose
backlog (the outstanding requests of the window's backlog line) does not
grow.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", default=[])
    ap.add_argument("--concurrency", type=int, nargs="+", default=[])
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench.lib.harness import run_cell
    loads = [("rate_per_s", r) for r in args.rates] + [("concurrency", c) for c in args.concurrency]
    for key, value in loads:
        print(f"=== {key} {value}", flush=True)
        result, _ = run_cell(args.workload, args.seed, args.seconds, False,
                             overrides={"traffic": {key: value}})
        print("SWEEP " + json.dumps({key: value, **result}), flush=True)


if __name__ == "__main__":
    main()
