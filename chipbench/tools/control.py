"""Readings that the limit of `correct` is set from, in one process.

    python3 chipbench/tools/control.py --workload yi6b.steady --seeds 101 102 103 \
        --seconds 10 [--warmup-s 20 | --warmup-steps 150]

For each seed: one run of the cell at its own load (a shorter warm-up and
window where given), then, on the same sample of served requests, the
program's reading (the widest gap of a served token below the float32
reference's best) and the control's (the same gap for the token that the
reference computed in fp8 puts first), which decides `correct` in this
mode and so has to make it false. Each is printed as one JSON line.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--warmup-s", type=float, default=None, help="open loop: warm-up seconds")
    ap.add_argument("--warmup-steps", type=int, default=None, help="closed loop: warm-up steps")
    ap.add_argument("--rate", type=float, default=None, help="override the mix's rate (open loop)")
    args = ap.parse_args(argv)
    from chipbench.lib.harness import run_cell
    traffic = {}
    if args.warmup_s is not None:
        traffic["warmup_s"] = args.warmup_s
    if args.warmup_steps is not None:
        traffic["warmup_steps"] = args.warmup_steps
    if args.rate is not None:
        traffic["rate_per_s"] = args.rate
    for seed in args.seeds:
        result, checks = run_cell(args.workload, seed, args.seconds, False,
                                  overrides={"traffic": traffic}, control=True)
        print("CONTROL " + json.dumps({"seed": seed, "correct": result["correct"],
                                       "metrics": result["metrics"],
                                       "control": result.get("control"),
                                       "checks": result["checks"]}), flush=True)


if __name__ == "__main__":
    main()
