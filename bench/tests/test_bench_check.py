"""The comparison that decides ``correct``, driven through a whole run
(the look for a chip aside) at a size the CPU holds: the program passes,
and the control and each fault a cell can have come out not correct."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench.entries.sort import make as sort_call
from bench.harness import measure
from bench.references import sort_reference as ref
from bench.spec import load_cell
from bench.traffic.datasets import DATASETS, make_dataset

SECONDS = 0.3


def small(name, **sizes):
    cell = load_cell(name)
    return cell._replace(cfg={**cell.cfg, **sizes})


TOPM = dict(batch=8, n=256)
FULL = dict(batch=4, n=512)
CELLS = [("topm_u8.extract_min", TOPM), ("fullsort_u32.full", FULL)]


def run(cell, call=None, seed=5):
    return measure(cell, seed, SECONDS, False, time.perf_counter(),
                   call=call)[1]


@pytest.mark.parametrize("name,sizes", CELLS)
def test_the_program_is_correct(name, sizes):
    line = run(small(name, **sizes))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"elems_per_s", "p95_ms", "setup_s"}


@pytest.mark.parametrize("name", [name for name, _ in CELLS])
def test_the_control_is_not_correct(name):
    # the control runs on the host, so it runs at the cell's own size
    cell = load_cell(name)
    line = run(cell, ref.control_call(cell.cfg))
    assert not line["correct"]
    assert line["checks"]["rows_wrong"]["value"] > 0


def faulty(cfg, fault):
    """``sort()`` with one fault planted where its answer is produced."""
    call = sort_call(cfg)

    def broken(x, stop_after):
        if fault == "half_batch":
            # half of the batch left out, its rows answered by the rest
            half = x.shape[0] // 2
            res = call(x[:half], stop_after)
            for f in ("indices", "values", "cycles", "drs", "reload_cycles"):
                v = getattr(res, f)
                if v is not None:
                    setattr(res, f, np.concatenate([v, v[:x.shape[0] - half]]))
            return res
        res = call(x, stop_after)
        if fault == "answer_altered":
            res.indices = res.indices.copy()
            res.indices[0, 0] = (res.indices[0, 0] + 1) % x.shape[-1]
        elif fault == "counter_altered":
            res.cycles = res.cycles + (np.arange(x.shape[0]) == 0)
        return res
    return broken


@pytest.mark.parametrize("name,sizes,fault", [
    ("topm_u8.extract_min", TOPM, "answer_altered"),
    ("topm_u8.extract_min", TOPM, "half_batch"),
    ("topm_u8.extract_min", TOPM, "counter_altered"),
    ("fullsort_u32.full", FULL, "answer_altered"),
    ("fullsort_u32.full", FULL, "half_batch"),
])
def test_a_fault_is_not_correct(name, sizes, fault):
    cell = small(name, **sizes)
    line = run(cell, faulty(cell.cfg, fault))
    assert not line["correct"], fault
    assert line["failed"] > 0


def test_a_call_that_raises_is_not_correct():
    cell = small("fullsort_u32.full", **FULL)

    call, calls = sort_call(cell.cfg), []

    def raises(x, stop_after):
        # set-up's call succeeds, every call of the window raises
        calls.append(1)
        if len(calls) > 1:
            raise RuntimeError("device lost")
        return call(x, stop_after)
    line = run(cell, raises)
    assert not line["correct"]
    assert line["checks"]["calls_raised"]["value"] == line["attempted"]


@pytest.mark.parametrize("k,stop_after", [(2, 32), (2, 1), (0, 5), (3, None)])
@pytest.mark.parametrize("dataset", DATASETS)
def test_plain_controller_matches_the_papers_oracle(dataset, k, stop_after):
    """The reference's controller against the program's own cycle-exact
    oracle, which the paper's worked examples pin."""
    from repro.core import ref_tns
    x = make_dataset(dataset, (3, 128), 16, np.random.default_rng(4))
    for row in x:
        want = ref_tns.tns_sort(row, width=16, k=k, stop_after=stop_after)
        assert ref.tns_counters(row, 16, k, stop_after) == (
            want.cycles, want.drs, want.reload_cycles)


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.uint32,
                                   np.uint64])
def test_reference_sorts_keys_in_their_own_dtype(dtype):
    # ascending keys are sorted as they come, descending ones negated in a
    # wider type; both agree with the order of the keys as exact integers
    x = make_dataset("random", (4, 300), 8 * np.dtype(dtype).itemsize,
                     np.random.default_rng(6)).astype(dtype)
    x[:, ::7] = x[:, :1]                                    # ties
    wide = [[int(v) for v in row] for row in x]
    for asc in (True, False):
        want = [sorted(range(300), key=lambda j: (r[j] if asc else -r[j], j))
                for r in wide]
        assert ref.permutation(x, asc, None).tolist() == want
        assert ref.permutation(x, asc, 3).tolist() == [w[:3] for w in want]


def test_reference_order_is_stable_and_exact():
    x = np.array([[3, 1, 3, 0, 1], [7, 7, 7, 7, 6]], np.uint32)
    assert ref.permutation(x, True, None).tolist() == [[3, 1, 4, 0, 2],
                                                      [4, 0, 1, 2, 3]]
    assert ref.permutation(x, True, 2).tolist() == [[3, 1], [4, 0]]
    assert ref.permutation(x, False, 2).tolist() == [[0, 2], [0, 1]]
