"""One generator for every traffic mix.

A traffic file fixes a *multiset* of (prompt length, output length) pairs
and of gaps between arrivals, as quantiles of its stated distributions.
Every seed has the file's one order of both: `--seed` draws the token ids
(and the weights) and never changes what is asked for or when, so two seeds
of one file ask for the same tokens at the same instants. An order that the
seed permuted, or began at a place of its choosing, changed which requests
overlap which arrivals and which come last, and with that the tail of the
gaps between tokens and the length of a batch job (PERF.md, Findings).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


def quantiles(spec: dict, n: int) -> List[float]:
    """`n` mid-point quantiles of the distribution `spec` states."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        inv = NormalDist().inv_cdf
        vals = [math.exp(mu + sigma * inv(q)) for q in qs]
    elif dist == "uniform":
        vals = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    elif dist == "exponential":
        vals = [-math.log(1.0 - q) * spec["mean"] for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    lo, hi = spec.get("min", -math.inf), spec.get("max", math.inf)
    return [min(max(v, lo), hi) for v in vals]


def length_pairs(traffic: dict) -> List[tuple]:
    """The file's fixed multiset of (prompt, output) lengths. Outputs are
    dealt to prompts by a fixed stride, so that the two are uncorrelated
    and the pairing is the same for every seed."""
    n = traffic["cycle"]
    prompts = [int(round(v)) for v in quantiles(traffic["prompt_tokens"], n)]
    outputs = [int(round(v)) for v in quantiles(traffic["output_tokens"], n)]
    stride = traffic.get("pair_stride", 7)
    if math.gcd(stride, n) != 1:
        raise ValueError(f"pair_stride {stride} shares a factor with cycle {n}")
    return [(prompts[i], outputs[(i * stride + n // 2) % n]) for i in range(n)]


def gap_multiset(traffic: dict) -> Optional[List[float]]:
    """Gaps between arrivals of one cycle (None for a closed backlog):
    quantiles of the exponential, scaled so that a cycle of `n` requests
    lasts exactly `n / rate` seconds whatever the order."""
    arrivals = traffic.get("arrivals")
    if not arrivals:
        return None
    n, rate = traffic["cycle"], arrivals["rate_per_s"]
    gaps = quantiles({"dist": "exponential", "mean": 1.0}, n)
    scale = (n / rate) / sum(gaps)
    return [g * scale for g in gaps]


@dataclass
class Schedule:
    prompt_lens: List[int]
    output_lens: List[int]
    due_s: Optional[List[float]]  # None: closed backlog
    seed: int
    vocab: int

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def prompt(self, i: int) -> List[int]:
        """Token ids of request `i`: drawn from (seed, i), so a request's
        content does not depend on how many were made before it."""
        rng = np.random.default_rng([self.seed, 1, i])
        return rng.integers(0, self.vocab, self.prompt_lens[i]).tolist()


def make_schedule(traffic: dict, seed: int, vocab: int, requests: int) -> Schedule:
    """At least `requests` requests, in whole cycles, each cycle the file's
    multiset in the one fixed order."""
    pairs, gaps = length_pairs(traffic), gap_multiset(traffic)
    n = len(pairs)
    order = np.random.default_rng([0]).permutation(n)
    prompt_lens, output_lens, due = [], [], []
    for cycle in range(-(-requests // n)):
        prompt_lens += [pairs[j][0] for j in order]
        output_lens += [pairs[j][1] for j in order]
        if gaps is not None:
            # a cycle's first request is due as the cycle opens, and a cycle
            # opens at a whole multiple of its length: no sum of gaps, whose
            # rounding would depend on their order, decides which window a
            # request falls in
            t = cycle * (n / traffic["arrivals"]["rate_per_s"])
            for j in order:
                due.append(t)
                t += gaps[j]
    return Schedule(prompt_lens, output_lens, due if gaps is not None else None,
                    seed, vocab)
