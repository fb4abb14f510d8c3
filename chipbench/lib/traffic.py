"""Request streams drawn from a traffic file and a seed.

A length is either fixed (`{"tokens": n}`) or follows a lognormal fitted
through the file's 25th, 50th and 75th percentiles (the arithmetic of
`serving/workload.py`, copied here so that the yardstick does not move
with the program). A draw whose prompt plus output passes the context
cap is drawn again.

Every seed serves the same population of sizes and inter-arrival gaps,
drawn once from `population_seed` in the traffic file; the run's seed
only permutes that population and draws the token ids. Two seeds thus
carry the same work in another order, so the spread between seeds is the
system's and not the sampler's.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, Optional

import numpy as np

Z75 = 0.6744897501960817  # Phi^-1(0.75)

# streams of one run: the measured one and the warm-up one
MEASURED, WARMUP = 0, 1


def lognormal_params(p25: float, p50: float, p75: float) -> tuple[float, float]:
    """(mu, sigma) of the lognormal through the three quartiles."""
    mu = math.log(max(p50, 1.0))
    sigma = math.log(max(p75, 1.0) / max(p25, 1.0)) / (2.0 * Z75)
    return mu, max(sigma, 1e-3)


@dataclasses.dataclass(frozen=True)
class Population:
    """The sizes every seed of a traffic mix serves, in file order."""
    prompt_lens: np.ndarray     # (N,) tokens each request adds after any shared prefix
    output_lens: np.ndarray     # (N,) new tokens each request asks for
    gaps: np.ndarray            # (N,) inter-arrival gaps at a rate of 1 per second
    groups: np.ndarray          # (N,) shared-prefix group, -1 where none

    def __len__(self) -> int:
        return len(self.prompt_lens)


def draw_population(spec: dict) -> Population:
    """The fixed population of a traffic file (independent of any run seed)."""
    rng = np.random.default_rng(int(spec["population_seed"]))
    n = int(spec["population"])
    cap = int(spec["context_cap"])
    shared = spec.get("shared_prefix") or {}
    pre = int(shared.get("tokens", 0))
    draw_p, draw_o = _length(spec["prompt"], cap), _length(spec["output"], cap)
    plen = np.empty(n, np.int64)
    olen = np.empty(n, np.int64)
    for i in range(n):
        while True:
            p, o = draw_p(rng), draw_o(rng)
            if pre + p + o <= cap:
                break
        plen[i], olen[i] = p, o
    gaps = rng.exponential(1.0, size=n)
    k = int(shared.get("groups", 0))
    groups = (np.arange(n) % k) if k else np.full(n, -1)
    return Population(plen, olen, gaps, groups)


def _length(dist: dict, cap: int):
    """A sampler of one length: fixed, or lognormal through the quartiles."""
    if dist.get("tokens") is not None:
        n = int(dist["tokens"])
        return lambda rng: n
    mu, sg = lognormal_params(dist["p25"], dist["p50"], dist["p75"])
    return lambda rng: int(np.clip(rng.lognormal(mu, sg), 1, cap))


def staggered(stream: Iterator["Draw"], first: int, seed: int, vocab: int,
              population_seed: int, block: int = 16) -> Iterator["Draw"]:
    """The warm-up of a closed loop, started as if it had run for a while.

    Its first `first` requests stand for requests already part way
    through: each keeps a share of its output (1 to all of it) and carries
    the rest as extra prompt tokens, rounded down to whole `block`-token
    pages. The queue then holds contexts spread over the whole range at
    once and finishes requests one after another, as in steady state, and
    the requests that replace them are the mix's own. The shares come
    from `population_seed` and not from the run's seed: every seed's queue
    runs through the same pattern of prefills and finishes, and the run's
    seed draws only token ids."""
    shares = np.random.default_rng(int(population_seed) + 1).random(first)
    rng = np.random.default_rng(stream_seed(seed, 3))
    for i, d in enumerate(stream):
        if i < first:
            keep = 1 + int(shares[i] * d.max_new)
            extra = (d.max_new - keep) // block * block
            d.prompt = np.concatenate(
                [d.prompt, rng.integers(0, vocab, size=extra, dtype=np.int32)])
            d.max_new = keep
        yield d


@dataclasses.dataclass
class Draw:
    """One request of a stream: due time (s after the stream starts, open
    loop only), its prompt token ids and the new tokens it asks for."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    group: int


def stream_seed(seed: int, which: int) -> np.random.SeedSequence:
    """The seed sequence of one stream of a run (measured or warm-up)."""
    return np.random.SeedSequence([int(seed) & 0xFFFFFFFF, int(seed) >> 32, which])


def system_prompts(spec: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """The shared system prompts of a mix, made from the run's seed."""
    shared = spec.get("shared_prefix") or {}
    k, n = int(shared.get("groups", 0)), int(shared.get("tokens", 0))
    rng = np.random.default_rng(stream_seed(seed, 2))
    return [rng.integers(0, vocab, size=n, dtype=np.int32) for _ in range(k)]


def requests(spec: dict, pop: Population, seed: int, which: int, vocab: int,
             prefixes: Optional[list[np.ndarray]] = None) -> Iterator[Draw]:
    """The endless stream `which` of run `seed`: successive permutations
    of the population, with due times at the file's rate (open loop)."""
    rng = np.random.default_rng(stream_seed(seed, which))
    rate = float(spec.get("rate_per_s") or 0.0)
    t = 0.0
    i = 0
    while True:
        for j in rng.permutation(len(pop)):
            if rate > 0:
                t += float(pop.gaps[j]) / rate
            body = rng.integers(0, vocab, size=int(pop.prompt_lens[j]), dtype=np.int32)
            g = int(pop.groups[j])
            if g >= 0 and prefixes:
                body = np.concatenate([prefixes[g], body])
            yield Draw(i, t, body, int(pop.output_lens[j]), g)
            i += 1
