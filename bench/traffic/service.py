"""The generator of a multi-tenant sort service's requests, driven by a
traffic file with ``"generator": "service"``.

The traffic file holds:

* ``pool``: how many distinct requests set-up makes; the open loop's
  schedule (``arrivals.py``) cycles through them in order;
* ``tenants`` and ``zipf_s``: how many tenants send requests, and the
  exponent of the Zipf law by which they share the pool (YCSB's default
  is 0.99).  Tenant ``t`` (from 0) sends ``(t + 1) ** -zipf_s`` of the
  pool, normalised, rounded by largest remainder;
* ``profile_seed``: the seed of the tenants' profiles.  Each tenant sends
  one kind of request: ``n`` log-uniform over the powers of two in
  ``n_range``, ``m`` from ``m_mix`` (pairs of ``[m, weight]``; ``null``
  is a full sort), a ``priority`` uniform over the whole numbers in
  ``priorities`` (``[lo, hi]``), a deadline uniform in ``deadline_ms``
  (``[lo, hi]`` wall milliseconds, or ``null`` for none), and one of the
  paper's datasets at its key width.  Widths come from the
  configuration's ``widths``, split by tenant (tenant ``t`` takes
  ``widths[t % len(widths)]``);
* ``objective``: the budget axis every request asks the service to
  minimise (``latency``, ``energy`` or ``wall``).

The profiles belong to the deployment, so they are drawn from
``profile_seed`` and not from the run's seed.  The run's seed draws the
order of the requests and their keys: every seed sends the same multiset
of request shapes, tenant by tenant, in another order.  Each request holds
one ``(1, n)`` row, so the reference compares it as a one-row batch.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.traffic.datasets import LAYOUTS, make_dataset

DTYPES = {8: np.uint8, 16: np.uint16, 32: np.uint32}


class Profile(NamedTuple):
    n: int
    stop_after: int | None      # m; None: full sort
    priority: int
    deadline_ms: float | None
    width: int
    dataset: str


class ServiceRequest(NamedTuple):
    x: np.ndarray               # (1, n) keys of the tenant's width
    stop_after: int | None
    dataset: str
    tenant: int
    priority: int
    deadline_ms: float | None
    objective: str


def zipf_counts(size: int, tenants: int, s: float) -> np.ndarray:
    """Requests per tenant: ``size`` shared by the Zipf law, rounded by
    largest remainder so that the counts add up to ``size``."""
    w = np.arange(1, tenants + 1, dtype=float) ** -s
    share = size * w / w.sum()
    counts = np.floor(share).astype(int)
    extra = np.argsort(-(share - counts), kind="stable")[:size - counts.sum()]
    counts[extra] += 1
    return counts


def profiles(cfg: dict, traffic: dict) -> list[Profile]:
    rng = np.random.default_rng(traffic["profile_seed"])
    lo, hi = (int(v).bit_length() - 1 for v in traffic["n_range"])
    if [2 ** lo, 2 ** hi] != list(traffic["n_range"]):
        raise ValueError(f"n_range {traffic['n_range']} must be powers of two")
    ms = [m for m, _ in traffic["m_mix"]]
    weights = np.array([w for _, w in traffic["m_mix"]], float)
    if any(m is not None and not 1 <= m <= 2 ** lo for m in ms):
        raise ValueError(f"m_mix {ms} must lie in 1..{2 ** lo} or be null")
    widths = cfg["widths"]
    if any(w not in LAYOUTS or w not in DTYPES for w in widths):
        raise ValueError(f"the paper gives {sorted(LAYOUTS)} bits, "
                         f"not {widths}")
    p_lo, p_hi = traffic["priorities"]
    cum = np.cumsum(weights) / weights.sum()
    out = []
    for t in range(traffic["tenants"]):
        # five uniforms per tenant, whatever the mix: changing one field's
        # range leaves the others' draws as they were
        u = rng.random(5)
        n = 2 ** (lo + int(u[0] * (hi - lo + 1)))
        m = ms[min(int(np.searchsorted(cum, u[1], side="right")),
                   len(ms) - 1)]
        priority = p_lo + int(u[2] * (p_hi - p_lo + 1))
        deadline = (None if traffic["deadline_ms"] is None else
                    float(np.interp(u[3], [0, 1], traffic["deadline_ms"])))
        width = widths[t % len(widths)]
        datasets = LAYOUTS[width]
        dataset = datasets[int(u[4] * len(datasets))]
        out.append(Profile(n, m, priority, deadline, width, dataset))
    return out


def make_pool(cfg: dict, traffic: dict, seed: int) -> list[ServiceRequest]:
    tenants = profiles(cfg, traffic)
    counts = zipf_counts(traffic["pool"], len(tenants), traffic["zipf_s"])
    rng = np.random.default_rng(seed)
    order = rng.permutation(np.repeat(np.arange(len(tenants)), counts))
    pool = []
    for t in order:
        p = tenants[t]
        x = make_dataset(p.dataset, (1, p.n), p.width, rng)
        pool.append(ServiceRequest(x.astype(DTYPES[p.width]), p.stop_after,
                                   p.dataset, int(t), p.priority,
                                   p.deadline_ms, traffic["objective"]))
    return pool
