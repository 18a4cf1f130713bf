"""The multi-tenant service generator: Zipf tenant shares, profiles that
belong to the deployment, the same shapes for every seed."""
from __future__ import annotations

import collections

import numpy as np
import pytest

from bench.traffic import service
from bench.traffic.datasets import LAYOUTS

CFG = {"widths": [8, 32]}
TRAFFIC = {"pool": 1024, "tenants": 64, "zipf_s": 0.99, "profile_seed": 0,
           "n_range": [256, 4096],
           "m_mix": [[1, 0.4], [8, 0.2], [32, 0.2], [None, 0.2]],
           "priorities": [0, 7], "deadline_ms": [50, 500],
           "objective": "latency"}
SEEDS = (0, 5, 2 ** 31 + 3)


def test_tenants_share_the_pool_by_the_zipf_law():
    counts = service.zipf_counts(1024, 64, 0.99)
    assert counts.sum() == 1024 and np.all(np.diff(counts) <= 0)
    want = 1024 * np.arange(1, 65.0) ** -0.99
    want /= want.sum() / 1024
    assert np.all(np.abs(counts - want) < 1)
    pool = service.make_pool(CFG, TRAFFIC, SEEDS[2])
    got = collections.Counter(r.tenant for r in pool)
    assert [got[t] for t in range(64)] == counts.tolist()


def test_profiles_belong_to_the_deployment_and_keep_their_ranges():
    a = service.profiles(CFG, TRAFFIC)
    assert a == service.profiles(CFG, TRAFFIC)
    assert a != service.profiles(CFG, {**TRAFFIC, "profile_seed": 1})
    # a deadline range changes the deadlines and no other field
    b = service.profiles(CFG, {**TRAFFIC, "deadline_ms": None})
    assert [p._replace(deadline_ms=None) for p in a] == b
    assert {p.n for p in a} <= {256, 512, 1024, 2048, 4096}
    assert len({p.n for p in a}) >= 4
    assert {p.stop_after for p in a} == {1, 8, 32, None}
    assert all(0 <= p.priority <= 7 and 50 <= p.deadline_ms <= 500
               for p in a)
    assert [p.width for p in a[:4]] == [8, 32, 8, 32]
    assert all(p.dataset in LAYOUTS[p.width] for p in a)


def test_every_seed_sends_the_same_shapes_in_another_order():
    pools = [service.make_pool(CFG, TRAFFIC, s) for s in SEEDS]
    shapes = [[(r.tenant, r.x.shape, r.x.dtype, r.stop_after) for r in p]
              for p in pools]
    assert len({tuple(sorted(map(repr, s))) for s in shapes}) == 1
    assert len({tuple(map(repr, s)) for s in shapes}) == len(SEEDS)
    again = service.make_pool(CFG, TRAFFIC, SEEDS[1])
    assert all(np.array_equal(p.x, q.x) for p, q in zip(pools[1], again))
    for r in pools[0]:
        assert r.x.shape[0] == 1 and r.x.dtype == service.DTYPES[
            8 if r.x.dtype == np.uint8 else 32]
        assert int(r.x.max()) < 2 ** (8 * r.x.dtype.itemsize)


@pytest.mark.parametrize("change,match", [
    ({"n_range": [256, 1000]}, "powers of two"),
    ({"m_mix": [[300, 1.0]]}, "m_mix"),
])
def test_a_mix_the_generator_cannot_make_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        service.make_pool(CFG, {**TRAFFIC, **change}, 0)


def test_a_width_the_paper_does_not_give_is_refused():
    with pytest.raises(ValueError, match="the paper gives"):
        service.make_pool({"widths": [16]}, TRAFFIC, 0)
