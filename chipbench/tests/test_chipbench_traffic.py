"""The generator: deterministic per seed, the same work for every seed,
and lengths as the traffic files state them."""
import itertools

import numpy as np
import pytest

from chipbench.lib import registry
from chipbench.lib.traffic import (MEASURED, WARMUP, draw_population, lognormal_params,
                                   requests, staggered, system_prompts)

MIXES = ["steady", "docbatch"]
# a shared-prefix mix, as a traffic file states one
SHARED = {"loop": "closed", "concurrency": 8, "shared_prefix": {"groups": 4, "tokens": 2048},
          "prompt": {"tokens": 160}, "output": {"tokens": 140}, "context_cap": 4096,
          "population": 4, "population_seed": 3}
BIG_SEED = 2**33 + 12345
# ShareGPT's quartiles (GreenLLM Table 2), as a variable-length mix states them
SHAREGPT = {"prompt": {"p25": 24, "p50": 160, "p75": 510},
            "output": {"p25": 24, "p50": 140, "p75": 357},
            "context_cap": 4096, "population": 512, "population_seed": 5, "rate_per_s": 2.0}


def take(spec, seed, which, n, vocab=64000):
    pop = draw_population(spec)
    return list(itertools.islice(
        requests(spec, pop, seed, which, vocab, system_prompts(spec, seed, vocab)), n))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_stream(mix):
    spec = registry.traffic(mix)
    a, b = take(spec, BIG_SEED, MEASURED, 50), take(spec, BIG_SEED, MEASURED, 50)
    assert [(d.due_s, d.max_new, d.prompt.tolist()) for d in a] == \
        [(d.due_s, d.max_new, d.prompt.tolist()) for d in b]
    c = take(spec, BIG_SEED + 1, MEASURED, 50)
    assert a[0].prompt.tolist() != c[0].prompt.tolist()


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_as_stated(mix):
    spec = registry.traffic(mix)
    exp = spec["expected"]
    for d in take(spec, BIG_SEED, MEASURED, 40) + take(spec, 3, WARMUP, 40):
        assert len(d.prompt) == exp["prompt_mean"] and d.max_new == exp["output_mean"]
        assert len(d.prompt) + d.max_new <= spec["context_cap"]


def test_staggered_warmup_starts_part_way():
    """The warm-up's first requests keep a uniform share of their output
    and carry the rest, in whole pages, as prompt: contexts spread over the
    mix's whole range from the start."""
    spec = registry.traffic("steady")
    p, o = spec["expected"]["prompt_mean"], spec["expected"]["output_mean"]
    draws = list(staggered(iter(take(spec, BIG_SEED, WARMUP, 400)), 300, BIG_SEED, 64000, 9))
    keep = [d.max_new for d in draws]
    assert all(1 <= x <= o for x in keep[:300]) and keep[300:] == [o] * 100
    assert np.mean(keep[:300]) == pytest.approx((o + 1) / 2, rel=0.1)
    for d in draws[:300]:
        extra = len(d.prompt) - p
        assert extra % 16 == 0 and o - d.max_new - 16 < extra <= o - d.max_new
    assert all(len(d.prompt) == p for d in draws[300:])
    again = list(staggered(iter(take(spec, BIG_SEED, WARMUP, 300)), 300, BIG_SEED, 64000, 9))
    assert [(d.max_new, d.prompt.tolist()) for d in again] == \
        [(d.max_new, d.prompt.tolist()) for d in draws[:300]]
    # another seed: the same shares, other token ids
    other = list(staggered(iter(take(spec, 5, WARMUP, 300)), 300, 5, 64000, 9))
    assert [d.max_new for d in other] == keep[:300]
    assert other[0].prompt.tolist() != draws[0].prompt.tolist()


def test_variable_lengths_capped_means():
    """A mix of ShareGPT's quartiles, after the 4096 cap: 200k draws give
    the means ISSUE-level planning used (428 in, 386 out)."""
    pop = draw_population(dict(SHAREGPT, population=200_000))
    assert (pop.prompt_lens + pop.output_lens).max() <= 4096
    assert pop.prompt_lens.mean() == pytest.approx(428, rel=0.01)
    assert pop.output_lens.mean() == pytest.approx(386, rel=0.01)


def test_variable_lengths_seeds_permute_one_population():
    n = SHAREGPT["population"]
    a, b = take(SHAREGPT, BIG_SEED, MEASURED, n), take(SHAREGPT, 7, MEASURED, n)
    w = take(SHAREGPT, BIG_SEED, WARMUP, n)
    sizes = lambda s: sorted((len(d.prompt), d.max_new) for d in s)  # noqa: E731
    assert sizes(a) == sizes(b) == sizes(w)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]
    assert a[-1].due_s == pytest.approx(b[-1].due_s, rel=1e-9)
    assert a[-1].due_s == pytest.approx(n / SHAREGPT["rate_per_s"], rel=0.15)


def test_lognormal_fit_through_quartiles():
    mu, sg = lognormal_params(24, 160, 510)
    x = np.random.default_rng(0).lognormal(mu, sg, size=400_000)
    q25, q50, q75 = np.percentile(x, [25, 50, 75])
    assert q50 == pytest.approx(160, rel=0.02)
    # the fit is through p50 and the p75/p25 ratio, as in serving/workload.py
    assert q75 / q25 == pytest.approx(510 / 24, rel=0.03)


def test_shared_prefix_groups():
    spec = SHARED
    k, n = spec["shared_prefix"]["groups"], spec["shared_prefix"]["tokens"]
    pre = system_prompts(spec, BIG_SEED, 64000)
    assert len(pre) == k and all(len(p) == n for p in pre)
    draws = take(spec, BIG_SEED, MEASURED, 40)
    for d in draws:
        assert d.prompt[:n].tolist() == pre[d.group].tolist()
    assert {d.group for d in draws} == set(range(k))
    assert all(len(d.prompt) == n + 160 and d.max_new == 140 for d in draws)
