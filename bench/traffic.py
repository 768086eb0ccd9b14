"""What every traffic generator shares: requests due at a time, and draws
that give every seed the same set of sizes in another order.

A ``Pool`` holds the ``n`` quantiles ``ppf((i + 0.5) / n)`` of a
distribution and hands them out in an order drawn from the seed, refilling
with a fresh order when it runs dry.  Any ``n`` consecutive draws are the
same multiset for every seed, so the work a run offers does not swing with
the seed while its order, its token ids and its weights do.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Callable, List, Optional

import numpy as np

_N01 = NormalDist()


@dataclasses.dataclass
class Item:
    """A request the client sends at ``due`` seconds after traffic start."""
    due: float
    prompt: np.ndarray
    max_new: int
    tag: Any = None          # the generator's own handle (session, client)


class Pool:
    def __init__(self, rng: np.random.Generator,
                 ppf: Callable[[float], float], n: int = 64):
        self.rng = rng
        self.values = [ppf((i + 0.5) / n) for i in range(n)]
        self.left: List[float] = []

    def draw(self) -> float:
        if not self.left:
            self.left = list(self.rng.permutation(self.values))
        return self.left.pop()


def lognormal(median: float, sigma: float, lo: float, hi: float):
    return lambda u: min(max(median * math.exp(sigma * _N01.inv_cdf(u)), lo),
                         hi)


def exponential(mean: float):
    return lambda u: -mean * math.log(1.0 - u)


def geometric_failures(p: float, cap: Optional[int] = None):
    """Failures before the first success, P(success) = p."""
    def ppf(u):
        k = int(math.floor(math.log(1.0 - u) / math.log(1.0 - p)))
        return min(k, cap) if cap is not None else k
    return ppf


def zipf_index(n: int, s: float):
    """Index 0..n-1 with P(i) proportional to 1 / (i + 1) ** s."""
    w = np.array([1.0 / (i + 1) ** s for i in range(n)])
    cdf = np.cumsum(w / w.sum())
    return lambda u: int(np.searchsorted(cdf, u, side="right"))


def round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n), dtype=np.int32)


def bursty_arrivals(rng: np.random.Generator, rate: float, burst: float,
                    burst_s: float, cycle_s: float,
                    horizon: float) -> List[float]:
    """Arrival times on [0, horizon), in cycles of ``cycle_s`` seconds.

    Each cycle holds one burst of ``burst_s`` seconds at ``burst * rate``,
    placed at an offset drawn from the seed, and ``rate`` elsewhere, so the
    gap between bursts averages ``cycle_s - burst_s``.  Each part of a
    cycle holds the same number of arrivals for every seed (its expected
    count, the fractions carried to the next cycle); given that count,
    Poisson arrivals are independent and uniform over the part, which is
    how they are placed.  A window of whole cycles therefore offers every
    seed the same load."""
    out: List[float] = []
    carry_off = carry_on = 0.0
    quiet = cycle_s - burst_s
    for c in range(int(math.ceil(horizon / cycle_s))):
        t0 = c * cycle_s
        b0 = float(rng.uniform(0.0, quiet))
        carry_off += rate * quiet
        carry_on += rate * burst * burst_s
        n_off, n_on = int(carry_off), int(carry_on)
        carry_off -= n_off
        carry_on -= n_on
        u = rng.uniform(0.0, quiet, size=n_off)
        out.extend(t0 + u + (u >= b0) * burst_s)
        out.extend(t0 + b0 + rng.uniform(0.0, burst_s, size=n_on))
    return sorted(float(t) for t in out if t < horizon)
