"""The one traffic generator: reads a mix file of ``bench/traffic`` and makes
the requests of a run from its seed.

A mix file is JSON with these keys:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when the last token of its previous one arrives) or ``"open"`` (arrivals
  at ``rate_per_s`` on average, exponential gaps, sent on schedule whether
  or not earlier ones have finished);
* ``prompt`` and ``output``: length distributions, each
  ``{"dist": "uniform", "lo", "hi"}`` or
  ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped to
  ``[lo, hi]``);
* ``block``: how many consecutive requests share one set of sizes.

Every seed serves the same work in another order.  The prompt lengths,
output lengths and arrival gaps are the ``block`` evenly spaced quantiles
of their distributions, in one fixed order that each stream of requests
cycles through.  In a closed loop client k starts its cycle k * block /
clients places further on, so with as many clients as sizes every round of
sends holds each size once; the seed only turns every cycle by the same
number of places, and draws the prompt tokens (uniform over the
vocabulary).  So the schedule of work is the same for every seed up to
which client sends what, and seeds differ in the tokens and the weights
only: a seed that reordered the sizes freely changed which long requests
overlapped, and with it the tails, by several percent.  Every request is
greedy, so its served tokens can be checked against the reference.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The n evenly spaced quantiles (i + 1/2) / n of a length spec."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["lo"] + u * (spec["hi"] + 1 - spec["lo"])
        x = np.floor(x)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, spec["lo"], spec["hi"]).astype(np.int64)


def _exp_quantiles(mean: float, n: int) -> np.ndarray:
    """n exponential gaps at evenly spaced quantiles, scaled so that their
    mean is exactly ``mean``: the offered rate is the one the mix states."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (mean / g.mean())


@dataclasses.dataclass
class Spec:
    """One request as the generator made it."""
    index: int
    prompt: np.ndarray
    max_new: int


ORDER_SEED = 20251015  # one fixed order of the sizes, the same for every seed


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int):
        if mix["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.block = int(mix.get("block", 16))
        if self.closed and not 1 <= self.clients <= self.block:
            raise ValueError("a closed loop needs 1 to block clients")
        order = np.random.Generator(np.random.PCG64(ORDER_SEED))
        self._plen = order.permutation(_quantiles(mix["prompt"], self.block))
        self._olen = order.permutation(_quantiles(mix["output"], self.block))
        self._gaps = (order.permutation(_exp_quantiles(
            1.0 / mix["rate_per_s"], self.block)) if not self.closed
            else None)
        self._shift = seed % self.block
        self._arrivals: list = []  # open loop: due offsets in seconds

    @property
    def closed(self) -> bool:
        return self.mix["loop"] == "closed"

    @property
    def clients(self) -> int:
        return int(self.mix["clients"]) if self.closed else 1

    @property
    def longest(self) -> tuple:
        """(longest prompt, most outputs) any request of this mix has."""
        return int(self._plen.max()), int(self._olen.max())

    def _slot(self, client: int, j: int) -> int:
        return (j + client * (self.block // self.clients) + self._shift) \
            % self.block

    def request(self, index: int, client: int = 0, j: int = None) -> Spec:
        """Request ``index`` of the run: the ``j``-th of ``client`` (of the
        one stream in an open loop, where ``j`` is ``index``)."""
        k = self._slot(client, index if j is None else j)
        rng = np.random.Generator(np.random.PCG64([self.seed, index]))
        prompt = rng.integers(0, self.vocab,
                              size=int(self._plen[k])).astype(np.int32)
        return Spec(index, prompt, int(self._olen[k]))

    def arrival(self, i: int) -> float:
        """Open loop: seconds after the window opens that request i is due."""
        while len(self._arrivals) <= i:
            n = len(self._arrivals)
            prev = self._arrivals[-1] if n else 0.0
            self._arrivals.append(prev + float(self._gaps[self._slot(0, n)]))
        return self._arrivals[i]
