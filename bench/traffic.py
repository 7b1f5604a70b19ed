"""The one traffic generator.  A traffic file (``bench/traffic/<mix>.json``)
gives its parameters; this turns them and ``--seed`` into requests.

Lengths and gaps are drawn by stratified quantiles: every block of
``STRATUM`` consecutive requests holds the same multiset of prompt
lengths, output lengths, greedy flags and (open loop) inter-arrival gaps,
shuffled within the block by the seed.  Two seeds therefore give the same
work in a different order, and the token ids differ.  A block is short
beside a window (an open-loop window holds several), so every window of
one mix carries about the same load.

Keys of a traffic file:
  loop          "open" (scheduled arrivals) or "closed" (the waiting
                queue is kept at n_slots or more)
  arrivals      open loop only: {"kind": "poisson", "rate_per_s": r}
  prompt        {"median", "sigma", "cap", "grid"}: lognormal length,
                capped, rounded up onto the grid
  output        {"median", "sigma", "cap", "min"}: lognormal new tokens
  sampler       {"top_k", "top_p", "temperature"} of every request
  greedy_share  share of requests decoded greedily (argmax)
"""
from __future__ import annotations

import bisect
import dataclasses
import math
from statistics import NormalDist

import numpy as np

STRATUM = 16


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray        # (L,) int32 token ids
    n_new: int
    greedy: bool
    seed: int                 # the request's own sampling seed
    arrival: float            # seconds after the window opens (open loop)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _lognormal_quantiles(n: int, median: float, sigma: float) -> list[float]:
    nd = NormalDist()
    return [median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
            for i in range(n)]


def prompt_lengths(spec: dict, n: int) -> list[int]:
    grid = sorted(spec["grid"])
    out = []
    for x in _lognormal_quantiles(n, spec["median"], spec["sigma"]):
        x = min(x, spec["cap"])
        out.append(grid[min(bisect.bisect_left(grid, math.ceil(x)),
                            len(grid) - 1)])
    return out


def output_lengths(spec: dict, n: int) -> list[int]:
    return [max(spec.get("min", 1), min(spec["cap"], round(x)))
            for x in _lognormal_quantiles(n, spec["median"], spec["sigma"])]


def exponential_gaps(rate: float, n: int) -> list[float]:
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


class Stream:
    """The mix's requests in order, made block by block as they are
    taken.  ``rate`` overrides the traffic file's arrival rate (the knee
    sweep)."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 rate: float | None = None):
        self.m = m = STRATUM
        self.vocab = vocab
        self.plen = prompt_lengths(traffic["prompt"], m)
        self.olen = output_lengths(traffic["output"], m)
        n_greedy = round(traffic["greedy_share"] * m)
        self.flags = [True] * n_greedy + [False] * (m - n_greedy)
        self.open_loop = traffic["loop"] == "open"
        if self.open_loop:
            rate = rate if rate is not None else (
                traffic["arrivals"]["rate_per_s"])
            if not rate or rate <= 0:
                raise ValueError("an open-loop mix needs arrivals.rate_per_s")
            self.gaps = exponential_gaps(rate, m)
        self._order = _rng(seed, 0)
        self._ids = _rng(seed, 1)
        self._block: list[Req] = []
        self._n = 0
        self._t = 0.0

    def _fill(self) -> None:
        m = self.m
        cols = [self._order.permutation(m) for _ in range(4)]
        for j in range(m):
            if self.open_loop:
                self._t += self.gaps[cols[3][j]]
            self._block.append(Req(
                rid=self._n,
                prompt=self._ids.integers(0, self.vocab,
                                          size=self.plen[cols[0][j]],
                                          dtype=np.int32),
                n_new=self.olen[cols[1][j]],
                greedy=self.flags[cols[2][j]],
                seed=int(self._ids.integers(0, 2 ** 31 - 1)),
                arrival=self._t if self.open_loop else 0.0,
            ))
            self._n += 1
        self._block.reverse()

    def peek(self) -> Req:
        if not self._block:
            self._fill()
        return self._block[-1]

    def take(self) -> Req:
        req = self.peek()
        self._block.pop()
        return req


def lengths_used(traffic: dict) -> list[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    return sorted(set(prompt_lengths(traffic["prompt"], STRATUM)))
