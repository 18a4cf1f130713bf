"""When the requests of an open loop are due, made from the seed.

A traffic file with ``"loop": "open"`` gives:

* ``rate``: the mean offered rate, requests per second;
* optionally bursts, as a two-state Markov-modulated Poisson process
  (after BurstGPT, arXiv:2401.17644): ``burst_factor``, how many times the
  calm rate a burst offers, and ``burst_s`` and ``calm_s``, the mean
  lengths of a burst and of a calm spell in seconds.  The calm rate is
  set so that the mean over both states is ``rate``.

Each spell's length and each gap between two arrivals of one state is an
exponential draw.  The draws come as a fixed multiset, the exponential's
quantiles, in an order drawn from the seed, and the spells fill the
window: every seed offers nearly the same number of requests in a
window, with the same spread of gaps and spells, so a seed changes when
the load comes and not how much of it.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Schedule(NamedTuple):
    due: np.ndarray     # seconds after the schedule's start, ascending
    burst: np.ndarray   # True where the request is due inside a burst


def exponentials(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` unit exponential draws as the quantiles at (j + 1/2) / n, in
    an order drawn from ``rng``."""
    return rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))


class _Draws:
    """Unit exponentials, one at a time, in batches of ``n``."""

    def __init__(self, n: int, rng: np.random.Generator):
        self.n, self.rng, self.left = n, rng, []

    def next(self) -> float:
        if not self.left:
            self.left = exponentials(self.n, self.rng).tolist()[::-1]
        return self.left.pop()


def rates(traffic: dict) -> tuple[float, float]:
    """(calm rate, burst rate) in requests per second."""
    rate, factor = float(traffic["rate"]), float(traffic.get("burst_factor",
                                                             1))
    if rate <= 0 or factor < 1:
        raise ValueError(f"rate {rate} must be > 0 and burst_factor "
                         f"{factor} >= 1")
    if factor == 1:
        return rate, rate
    calm_s, burst_s = float(traffic["calm_s"]), float(traffic["burst_s"])
    calm = rate * (calm_s + burst_s) / (calm_s + factor * burst_s)
    return calm, factor * calm


def schedule(traffic: dict, seed: int, seconds: float) -> Schedule:
    """Every request due in [0, ``seconds``).  With bursts, the window
    holds k = round(``seconds`` / (``calm_s`` + ``burst_s``)) calm spells
    and k bursts, alternating, calm first, their lengths the quantiles of
    their exponentials scaled together to fill the window: the share of
    time in bursts is the same for every seed."""
    rng = np.random.default_rng([seed, 1])   # apart from the pool's draws
    calm, burst = rates(traffic)
    if calm == burst:
        spells = [(seconds, calm, False)]
    else:
        calm_s, burst_s = float(traffic["calm_s"]), float(traffic["burst_s"])
        k = max(1, round(seconds / (calm_s + burst_s)))
        lengths = np.stack([calm_s * exponentials(k, rng),
                            burst_s * exponentials(k, rng)], 1).reshape(-1)
        lengths *= seconds / lengths.sum()
        spells = [(d, burst if j % 2 else calm, bool(j % 2))
                  for j, d in enumerate(lengths)]
    gaps = _Draws(max(1, round(traffic["rate"] * seconds)), rng)
    due, flag, start = [], [], 0.0
    for length_s, rate, b in spells:
        # a Poisson process forgets: each spell starts its own gaps
        end, t = min(start + length_s, seconds), start
        while True:
            t += gaps.next() / rate
            if t >= end:
                break
            due.append(t)
            flag.append(b)
        start = end
    return Schedule(np.array(due), np.array(flag, bool))
