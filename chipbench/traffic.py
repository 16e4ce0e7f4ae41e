"""Traffic from a mix file and a seed: the one general generator.

A mix file (``chipbench/traffic/<mix>.json``) fixes the multiset of
(prompt length, output length) pairs as quantiles of its two length
distributions, paired by one fixed permutation. The run's ``--seed``
decides only the order of the pairs, the token ids, the arrival times and
which caller sends what: two seeds send the same work in another order.
(With lengths resampled per seed, the median prompt of ~150 heavy-tailed
draws alone moves a median TTFT by several percent.)

No JAX, no numpy: the process that generates load imports neither.
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass
from statistics import NormalDist

HERE = os.path.dirname(os.path.abspath(__file__))
PAIRING_SEED = 0  # which output length goes with which prompt length


@dataclass(frozen=True)
class Request:
    index: int          # position in the schedule (also the x-request-id suffix)
    prompt_len: int
    output_len: int
    due: float          # seconds from window open; the ramp is negative


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic mix {name}: loop must be open or closed")
    return mix


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """n lengths at the mid-point quantiles (i + 0.5) / n of ``dist``."""
    if dist["kind"] == "fixed":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["kind"] == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        nd = NormalDist()
        raw = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in qs]
    elif dist["kind"] == "uniform":
        raw = [lo + q * (hi - lo) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return [min(hi, max(lo, int(round(x)))) for x in raw]


def length_pairs(mix: dict, n: int) -> list[tuple[int, int]]:
    """The mix's multiset of n (prompt, output) pairs; no run seed enters."""
    prompts = quantile_lengths(mix["prompt_len"], n)
    outputs = quantile_lengths(mix["output_len"], n)
    random.Random(PAIRING_SEED).shuffle(outputs)
    return list(zip(prompts, outputs))


def open_loop_schedule(mix: dict, seed: int, seconds: float) -> list[Request]:
    """A Poisson process conditioned on its count: exactly rate x seconds
    arrivals at sorted uniform times inside the window, carrying the mix's
    pairs in an order the seed picks, and rate x ramp more of the same
    pairs before it. Times are seconds from window open."""
    rate, ramp = float(mix["rate"]), float(mix["ramp_s"])
    rng = random.Random(f"{seed}:open")
    n, n_ramp = int(round(rate * seconds)), int(round(rate * ramp))
    pairs = length_pairs(mix, n)
    rng.shuffle(pairs)
    lead = rng.sample(pairs, min(n_ramp, n))
    dues = (sorted(rng.uniform(-ramp, 0.0) for _ in lead)
            + sorted(rng.uniform(0.0, seconds) for _ in pairs))
    return [Request(i, p, o, due)
            for i, (due, (p, o)) in enumerate(zip(dues, lead + pairs))]


def closed_loop_queue(mix: dict, seed: int) -> list[tuple[int, int]]:
    """One seeded shuffle of the mix's pair list; callers take the next
    pair from it in turn and the list is cycled."""
    rng = random.Random(f"{seed}:closed")
    pairs = length_pairs(mix, int(mix["pairs"]))
    rng.shuffle(pairs)
    return pairs


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Token ids of request ``index``: unique per request, so no two
    prompts share a prefix block by more than chance."""
    rng = random.Random(f"{seed}:tok:{index}")
    return [rng.randrange(vocab) for _ in range(length)]
