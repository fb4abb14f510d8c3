"""Golden refactor-parity: the steppable simulator must be bit-compatible.

The fixture tests/data/golden_simulate.json was captured from the
pre-refactor closure-based `simulate()` (tests/capture_golden.py). Every
per-request ReqTrace field and per-chip ChipUse aggregate must reproduce
EXACTLY (== on floats, not approx): the refactor reorganized control flow,
it must not change a single arithmetic operation or RNG draw.
"""
import functools
import json
import math
import operator
import os

import pytest

from repro.configs import get_config
from repro.serving.simulator import ServingMode, simulate
from repro.serving.workload import DATASETS, sample_mixture_requests

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "data", "golden_simulate.json")

CASES = {
    "standalone": ServingMode("standalone", "standalone", "a100"),
    "spec": ServingMode("spec", "spec", "a100", spec_k=4, acceptance=0.7),
    "dsd": ServingMode("dsd", "dsd", "a100", "t4", spec_k=4, acceptance=0.7),
    "dpd": ServingMode("dpd", "dpd", "a100", "v100"),
}


def _eq(a, b):
    """Bit-exact equality that treats NaN == NaN (unfinished-request fields)."""
    if isinstance(a, float) and isinstance(b, float) \
            and math.isnan(a) and math.isnan(b):
        return True
    return a == b


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("kind", sorted(CASES))
def test_simulate_matches_pre_refactor_golden(golden, kind):
    p = golden["params"]
    ds = DATASETS[p["dataset"]]
    reqs = sample_mixture_requests(ds, p["qps"], p["duration_s"],
                                   seed=p["workload_seed"])
    mode = CASES[kind]
    draft = get_config(p["draft"]) if mode.kind in ("spec", "dsd") else None
    res = simulate(mode, get_config(p["target"]), reqs, draft_cfg=draft,
                   seed=p["sim_seed"], start_s=p["start_s"])
    want = golden["cases"][kind]

    assert res.duration_s == want["duration_s"]
    assert res.start_s == want["start_s"]
    assert res.link_bytes == want["link_bytes"]
    assert res.link_busy_s == want["link_busy_s"]
    assert res.total_tokens == want["total_tokens"]

    assert len(res.traces) == len(want["traces"])
    for t, w in zip(res.traces, want["traces"]):
        for field in ("ttft_s", "finish_s", "tokens_out",
                      "first_token_s", "last_token_s"):
            got = getattr(t, field) if field != "req_id" else t.req.req_id
            assert _eq(got, w[field]), \
                f"{kind} req {t.req.req_id} {field}: {got} != {w[field]}"
        assert t.req.req_id == w["req_id"]

    assert sorted(res.use) == sorted(want["use"])
    for name, wu in want["use"].items():
        u = res.use[name]
        assert u.busy_s == wu["busy_s"], f"{kind}/{name} busy_s"
        assert u.energy_j == wu["energy_j"], f"{kind}/{name} energy_j"
        assert u.instances == wu["instances"]
        assert len(u.segments) == wu["n_segments"]
        if wu["seg_first"] is not None:
            assert list(u.segments[0]) == wu["seg_first"]
            assert list(u.segments[-1]) == wu["seg_last"]
        # left-to-right fold, as the golden was captured: Python >= 3.12's
        # float sum() is compensated and can differ from it in the last ulp
        assert functools.reduce(operator.add, (s[2] for s in u.segments),
                                0) == wu["seg_sum_energy"]
