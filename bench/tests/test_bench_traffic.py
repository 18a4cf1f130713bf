"""Request pools: the same per seed, different across seeds, the same
work for every seed; the datasets stay in range."""
from __future__ import annotations

import collections
import json

import numpy as np
import pytest

from bench.spec import ROOT, load_cell
from bench.traffic.datasets import DATASETS, LAYOUTS, make_dataset
from bench.traffic import generate
from bench.traffic.generate import make_pool, pool_in_background

SEEDS = (0, 7, 2 ** 31 + 11, 4_000_000_007)


def small(name):
    cell = load_cell(name)
    return {**cell.cfg, "batch": 2, "n": 64}, cell.traffic


CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_pool_is_a_function_of_the_seed(name):
    cfg, traffic = small(name)
    a, b = make_pool(cfg, traffic, SEEDS[2]), make_pool(cfg, traffic, SEEDS[2])
    assert len(a) == traffic["pool"]
    assert all(np.array_equal(p.x, q.x) and p[1:] == q[1:]
               for p, q in zip(a, b))
    assert all(p.x.shape == (2, 64) and p.x.dtype == np.dtype(cfg["dtype"])
               for p in a)
    c = make_pool(cfg, traffic, SEEDS[3])
    assert not all(np.array_equal(p.x, q.x) for p, q in zip(a, c))


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_gets_the_same_work_in_another_order(name):
    cfg, traffic = small(name)
    mixes = [[(p.dataset, p.stop_after) for p in make_pool(cfg, traffic, s)]
             for s in SEEDS]
    counts = [collections.Counter(m) for m in mixes]
    assert all(c == counts[0] for c in counts)
    assert len(set(counts[0].values())) == 1       # equal shares
    assert len({tuple(m) for m in mixes}) > 1      # another order


def test_a_pool_that_cannot_share_the_mix_equally_is_refused():
    cfg, traffic = small("fullsort_u32.full")
    with pytest.raises(ValueError, match="multiple"):
        make_pool(cfg, {**traffic, "pool": 12}, 0)


def test_a_pool_made_in_the_background_is_the_same_pool():
    cfg, traffic = small("topm_u8.extract_min")
    wait = pool_in_background(cfg, traffic, SEEDS[3])
    a, b = wait(), make_pool(cfg, traffic, SEEDS[3])
    assert len(a) == len(b)
    assert all(np.array_equal(p.x, q.x) and p[1:] == q[1:]
               for p, q in zip(a, b))


def test_a_failure_in_the_background_reaches_the_caller():
    cfg, traffic = small("topm_u8.extract_min")
    wait = pool_in_background(cfg, {**traffic, "pool": 4}, 0)
    with pytest.raises(ValueError, match="multiple"):
        wait()


@pytest.mark.parametrize("width,dataset", [(16, "random"), (30, "normal"),
                                           (8, "kruskal"), (8, "mapreduce")])
def test_a_layout_the_paper_does_not_give_is_refused(width, dataset):
    cfg, traffic = small("fullsort_u32.full")
    with pytest.raises(ValueError, match="the paper gives"):
        make_pool({**cfg, "width": width}, {**traffic, "datasets": [dataset],
                                             "pool": 2}, 0)


@pytest.mark.parametrize("name", CELLS)
def test_the_pool_outgrows_the_host_caches(name):
    # a pool the size of a large L3 (96 MiB) would measure the cache; each
    # cell's pool holds more keys than that, so the window reads DRAM
    cell = load_cell(name)
    nbytes = (cell.traffic["pool"] * cell.cfg["batch"] * cell.cfg["n"]
              * np.dtype(cell.cfg["dtype"]).itemsize)
    assert nbytes > 96 * 2 ** 20
    assert cell.cfg["n"] == 1024 and cell.cfg["width"] in LAYOUTS
    assert set(cell.traffic["datasets"]) <= set(LAYOUTS[cell.cfg["width"]])


@pytest.mark.parametrize("width,name", [(w, d) for w, ds in LAYOUTS.items()
                                        for d in ds])
def test_datasets_stay_in_range(name, width):
    x = make_dataset(name, (4, 4096), width, np.random.default_rng(1))
    assert x.shape == (4, 4096) and x.dtype == np.uint64
    assert int(x.max()) < 2 ** width
    # nothing piles up at the top of the range by clipping, but for the
    # zipf tail of the word counts, which the original clips the same way
    if name != "mapreduce":
        assert (x == 2 ** width - 1).mean() < 0.01


def test_clustered_keeps_the_papers_centres_at_8_and_32_bits():
    rng = np.random.default_rng(2)
    x8 = make_dataset("clustered", 20000, 8, rng).astype(float)
    assert abs(np.median(x8[x8 < 150]) - 100) < 2
    x32 = make_dataset("clustered", 20000, 32, rng).astype(float)
    assert abs(np.median(x32[x32 > 2 ** 24]) - 2 ** 25) < 2 ** 10
    assert set(DATASETS) == set(LAYOUTS[32]) > set(LAYOUTS[8])


# sha256 of every request (keys, shape, dtype, stop_after, dataset) of the
# full-size pool, recorded with the generator before cells could name
# their own: a traffic file that names no generator still gets these
PARENT_POOLS = {
    ("topm_u8.extract_min", 0): "7b91eab6a419af8c48487355fcf392d2"
                                "f84b6a1b970c86041eca7f8b35250f35",
    ("topm_u8.extract_min", 1): "4ce7114dd1359eedcf32c73e582f26ae"
                                "0bc5400a799eb8d46b8176b7ee5cd908",
    ("topm_u8.extract_min", 2): "c735feb181234dc2dfd5d5596538dbf2"
                                "6d7c83b3a07a0ad93e7558d92a7d7426",
    ("topm_u8.extract_min", 3): "29649a05e5efb35fb7fe963bdf22498c"
                                "57026e295436e22d5509b2b3094b5a72",
    ("fullsort_u32.full", 0): "f40882f6acd441f97d0d0a86bc9720f9"
                              "7619294203aace8d9f6d89c94044e84c",
    ("fullsort_u32.full", 1): "bd152d4b5977910e5f0efca4a732f74b"
                              "aa8d21874ff5038e51d3e0e18405a1e9",
    ("fullsort_u32.full", 2): "374fd4fb1782957846cdb43c400a3e61"
                              "f8f7df72515706382ac848563ddbb097",
    ("fullsort_u32.full", 3): "8c8b422fa44fd80507179cca79c60009"
                              "a890820f4f40fce41e846c4caa3c3a65",
}


@pytest.mark.parametrize("name,seed", sorted(PARENT_POOLS))
def test_the_existing_cells_pools_are_bit_identical(name, seed):
    import hashlib
    cell = load_cell(name)
    assert cell.generator.__file__ == generate.__file__
    h = hashlib.sha256()
    for r in cell.generator.make_pool(cell.cfg, cell.traffic, seed):
        h.update(r.x.tobytes())
        h.update(repr((r.x.shape, str(r.x.dtype), r.stop_after,
                       r.dataset)).encode())
    assert h.hexdigest() == PARENT_POOLS[name, seed]
