"""The host span recorder (``repro.runtime.spans``) and the ``sort.*``
spans and counters that ``sort()`` writes: nothing is kept with no
profiler running; under a profiler each call gives one root span, its
children and the transfer counters worked out from the shapes."""
from __future__ import annotations

import glob
import sys
import threading

import jax
import numpy as np
import pytest

from repro import sort as S
from repro.core import radix_select as rs
from repro.runtime import spans

B, N = 4, 128
X8 = np.random.default_rng(3).integers(0, 256, (B, N)).astype(np.uint8)
X32 = np.random.default_rng(4).integers(0, 2**32, (B, N), dtype=np.uint32)
X32_CELL = np.random.default_rng(5).integers(0, 2**32, (128, 1024),
                                             dtype=np.uint32)
X32_LONG = np.random.default_rng(6).integers(
    0, 2**32, (1, rs.DENSE_MAX_N + 1), dtype=np.uint32)
RADIX_SPANS = ["sort.encode", "sort.h2d", "sort.dispatch", "sort.readback",
               "sort.finish"]

# case: (engine, input, stop_after, child spans in order, counters)
CALLS = {
    "pallas-tns": ("pallas-tns", X8, 1,
                   ["sort.encode", "sort.h2d", "sort.dispatch",
                    "sort.readback", "sort.finish"],
                   # 8 uint8 planes in; one int32 array out: the four
                   # counter lanes and the permutation's first slot, found
                   # on the device
                   {"h2d_bytes": B * 8 * N, "readbacks": 1,
                    "d2h_bytes": B * (4 + 1) * 4, "device_perm": 1}),
    # past DEVICE_PERM_MAX emissions the rank ring comes back with the
    # counter lanes, still in one readback, and the host inverts it
    "pallas-tns-ring": ("pallas-tns", X8, 33,
                        ["sort.encode", "sort.h2d", "sort.dispatch",
                         "sort.readback", "sort.rank_to_perm",
                         "sort.finish"],
                        {"h2d_bytes": B * 8 * N, "readbacks": 1,
                         "d2h_bytes": B * (4 + N) * 4, "device_perm": 0}),
    "radix": ("radix", X32, None, RADIX_SPANS,
              {"h2d_bytes": B * N * 4, "readbacks": 1,
               "d2h_bytes": B * N * 4, "radix_dense": 1}),
    # the full-sort cell's shape takes the dense one-hot passes; a row
    # longer than their bound takes the gather passes
    "radix-dense": ("radix", X32_CELL, None, RADIX_SPANS,
                    {"h2d_bytes": X32_CELL.size * 4, "readbacks": 1,
                     "d2h_bytes": X32_CELL.size * 4, "radix_dense": 1}),
    "radix-long": ("radix", X32_LONG, None, RADIX_SPANS,
                   {"h2d_bytes": X32_LONG.size * 4, "readbacks": 1,
                    "d2h_bytes": X32_LONG.size * 4, "radix_dense": 0}),
}


def call(case):
    engine, x, m, _, _ = CALLS[case]
    return S.sort(x, engine=engine, k=2, stop_after=m)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One call per case under a profiler, after a warm-up call each
    with none running; (records kept by the warm-up, records of the traced
    calls, the trace's host event names, both calls' results)."""
    spans.clear()
    plain = [call(case) for case in CALLS]
    idle = spans.records()
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        recorded = [call(case) for case in CALLS]
    recs = spans.records()
    spans.clear()
    (path,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
    names = {e.name
             for p in jax.profiler.ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    return idle, recs, names, list(zip(plain, recorded))


def test_nothing_is_kept_without_a_profiler(traced):
    assert traced[0] == []


@pytest.mark.parametrize("case", list(CALLS))
def test_one_root_per_call_with_children_and_counters(traced, case):
    recs = traced[1]
    roots = [i for i, r in enumerate(recs) if r.parent == -1]
    assert [recs[i].name for i in roots] == ["sort"] * len(CALLS)
    i = roots[list(CALLS).index(case)]
    root = recs[i]
    kids = [r for r in recs if r.call == root.call and r.parent != -1]
    _, _, _, names, counts = CALLS[case]
    assert [r.name for r in kids] == names
    assert all(r.parent == i for r in kids)
    assert all(root.start_ns <= r.start_ns <= r.end_ns <= root.end_ns
               for r in kids)
    assert root.counts == counts
    assert all(r.counts is None for r in kids)


def test_spans_show_on_the_trace_host_plane(traced):
    names = traced[2]
    assert {n for _, _, _, ns, _ in CALLS.values() for n in ns} | {"sort"} \
        <= names


def test_results_are_unchanged_by_recording(traced):
    for plain, recorded in traced[3]:
        for f in ("indices", "values", "cycles", "drs", "reload_cycles"):
            a, b = getattr(plain, f), getattr(recorded, f)
            assert (a is None and b is None) or np.array_equal(a, b), f


@pytest.fixture
def profiling(tmp_path):
    """A profiler session recording host events."""
    jax.profiler.start_trace(str(tmp_path))
    assert jax.profiler.TraceAnnotation.is_enabled()
    yield
    jax.profiler.stop_trace()


def test_pallas_tns_records_only_the_call_steps(profiling):
    # the engine takes its grid shape from its arguments: no step of the
    # call looks anything up
    spans.clear()
    S.sort(X8, engine="pallas-tns", k=2, stop_after=1)
    recs = spans.records()
    spans.clear()
    assert [r.name for r in recs if r.parent != -1] == [
        "sort.encode", "sort.h2d", "sort.dispatch", "sort.readback",
        "sort.finish"]


def test_nesting_counters_and_overflow_of_a_recorder(profiling):
    rec = spans.Recorder(capacity=3)
    with rec.span("a"):
        rec.count("n", 2)
        with rec.span("b"):
            rec.count("n", 3)
            with rec.span("c"):
                pass
            with rec.span("d"):      # the buffer holds 3: dropped
                rec.count("n", 1)
    rec.count("n", 9)                # no root open: not kept
    with rec.span("e"):
        pass
    r = rec.records()
    assert [(x.name, x.call, x.parent) for x in r] == [
        ("a", 0, -1), ("b", 0, 0), ("c", 0, 1)]
    assert r[0].counts == {"n": 6} and r[1].counts is None
    assert rec.dropped == 2
    rec.clear()
    assert rec.records() == [] and rec.dropped == 0


def test_threads_recording_at_once_lose_nothing(profiling):
    rec, threads, per = spans.Recorder(), 8, 200

    def work():
        for _ in range(per):
            with rec.span("root"):
                rec.count("n", 1)
                with rec.span("child"):
                    pass
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in ts)
    r = rec.records()
    roots = [x for x in r if x.parent == -1]
    assert len(r) == 2 * threads * per and len(roots) == threads * per
    # a thread's span is a root while another thread has one open
    assert sorted(x.call for x in roots) == list(range(threads * per))
    assert all(x.counts == {"n": 1} for x in roots)
    assert all(r[x.parent].name == "root" and r[x.parent].call == x.call
               for x in r if x.parent != -1)
