"""Run a cell several times, each run its own process, and keep the
result lines.

    python3 chipbench/tools/repeat.py --workload yi6b.steady --seconds 30 \
        --seeds 11 12 13 --trace 0 --out chiprun_out/steady.jsonl

This parent process never imports JAX, so each child has the chip to
itself. Each run's result line is appended to --out with its seed, exit
code and wall time; its last output lines go to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tail", type=int, default=12, help="output lines to show per run")
    ap.add_argument("--overrides", default=None, help="passed on to run.py")
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    for seed in args.seeds:
        cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds", str(args.seconds),
               "--trace", str(args.trace)]
        if args.overrides:
            cmd += ["--overrides", args.overrides]
        t = time.perf_counter()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t
        lines = p.stdout.strip().splitlines()
        last = lines[-1] if lines else ""
        print(f"--- {args.workload} seed {seed} rc {p.returncode} wall {wall:.1f} s", flush=True)
        print("\n".join(lines[-args.tail:-1]), flush=True)
        print("\n".join(p.stderr.strip().splitlines()[-3:]), flush=True)
        try:
            result = json.loads(last)
        except json.JSONDecodeError:
            result, bad = None, bad + 1
        with open(args.out, "a") as f:
            f.write(json.dumps({"workload": args.workload, "seed": seed, "rc": p.returncode,
                                "wall_s": wall, "trace": args.trace, "result": result}) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
