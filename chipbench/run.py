"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, limits and metrics are found by
the names in BENCHMARK.json (chipbench/lib/registry.py). The last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics with --trace 0, its per-layer
metrics with --trace 1), `device`, with --trace 1 `breakdown`, and last
`checks`, each number compared with its limit. The same numbers end the
standard error. Without a TPU of a kind in chipbench/peaks.json, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's reduced trace data (JSON) to DIR")
    ap.add_argument("--overrides", default=None, metavar="FILE",
                    help="a JSON file of config/traffic/limits keys that replace the cell's "
                         "(for the tools; the benchmark's own runs give none)")
    args = ap.parse_args(argv)
    overrides = None
    if args.overrides:
        with open(args.overrides) as f:
            overrides = json.load(f)

    from chipbench.lib.harness import NoChip, run_cell
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                  t_process=T_PROCESS, keep_trace=args.keep_trace,
                                  overrides=overrides)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
