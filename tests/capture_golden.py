"""Capture golden parity fixtures for the steppable-simulator refactor.

Run ONCE against the pre-refactor `simulate()` to freeze its exact outputs:

    PYTHONPATH=src python tests/capture_golden.py

Writes tests/data/golden_simulate.json with per-request ReqTrace fields and
per-chip ChipUse aggregates for a fixed (mode, workload, seed) grid. The
refactored simulator must reproduce every value bit-exactly
(tests/test_parity_golden.py); floats survive the JSON round-trip exactly
because Python serializes doubles with repr precision.
"""
import functools
import json
import operator
import os

from repro.configs import get_config
from repro.serving.simulator import ServingMode, simulate
from repro.serving.workload import DATASETS, sample_mixture_requests

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "golden_simulate.json")

DS = DATASETS["sharegpt"]
T7 = get_config("llama-7b")
D1 = get_config("llama-1b")

CASES = {
    "standalone": ServingMode("standalone", "standalone", "a100"),
    "spec": ServingMode("spec", "spec", "a100", spec_k=4, acceptance=0.7),
    "dsd": ServingMode("dsd", "dsd", "a100", "t4", spec_k=4, acceptance=0.7),
    "dpd": ServingMode("dpd", "dpd", "a100", "v100"),
}
QPS, DUR, WORKLOAD_SEED, SIM_SEED, START_S = 4.0, 25.0, 11, 7, 3.0


def run_case(mode: ServingMode):
    reqs = sample_mixture_requests(DS, QPS, DUR, seed=WORKLOAD_SEED)
    draft = D1 if mode.kind in ("spec", "dsd") else None
    res = simulate(mode, T7, reqs, draft_cfg=draft, seed=SIM_SEED,
                   start_s=START_S)
    return {
        "duration_s": res.duration_s,
        "start_s": res.start_s,
        "link_bytes": res.link_bytes,
        "link_busy_s": res.link_busy_s,
        "total_tokens": res.total_tokens,
        "traces": [
            {
                "req_id": t.req.req_id,
                "ttft_s": t.ttft_s,
                "finish_s": t.finish_s,
                "tokens_out": t.tokens_out,
                "first_token_s": t.first_token_s,
                "last_token_s": t.last_token_s,
            }
            for t in res.traces
        ],
        "use": {
            name: {
                "busy_s": u.busy_s,
                "energy_j": u.energy_j,
                "instances": u.instances,
                "n_segments": len(u.segments),
                "seg_first": list(u.segments[0]) if u.segments else None,
                "seg_last": list(u.segments[-1]) if u.segments else None,
                # left-to-right fold: Python >= 3.12's float sum() is
                # compensated, which is not the sum the golden froze
                "seg_sum_energy": functools.reduce(
                    operator.add, (s[2] for s in u.segments), 0),
            }
            for name, u in sorted(res.use.items())
        },
    }


def main():
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    golden = {
        "params": {"dataset": "sharegpt", "qps": QPS, "duration_s": DUR,
                   "workload_seed": WORKLOAD_SEED, "sim_seed": SIM_SEED,
                   "start_s": START_S, "target": "llama-7b", "draft": "llama-1b"},
        "cases": {name: run_case(mode) for name, mode in CASES.items()},
    }
    with open(OUT, "w") as f:
        json.dump(golden, f, indent=1, sort_keys=True)
    print(f"wrote {OUT}")
    for name, case in golden["cases"].items():
        print(f"  {name}: {len(case['traces'])} reqs, "
              f"{case['total_tokens']} tokens, dur={case['duration_s']:.3f}s")


if __name__ == "__main__":
    main()
