"""The program's spans on the trace's clock (bench/program_spans.py) and
the metrics that read them: the bracketing alignment, the per-call means
and the idle share on synthetic intervals, a program without the
recorder, and the alignment on a real host trace of ``sort()``."""
from __future__ import annotations

import glob

import jax
import numpy as np
import pytest

from bench import program_spans as ps
from bench import trace as tr
from bench.harness import Run, Window
from bench.spec import BENCH, load_module
from repro.runtime.spans import Record

E = tr.Event
SHIFT = 5000     # the recorder's clock runs this far ahead of the trace's


def rec(name, start, end, call, parent, counts=None):
    """A record whose times are given on the trace's clock."""
    return Record(name, start + SHIFT, end + SHIFT, call, parent,
                  ({} if counts is None else counts) if parent < 0 else None)


def synthetic():
    """Window 0-1000 ns, two calls, the device busy inside each call's
    readback."""
    calls = [E("sort_call", 100, 400), E("sort_call", 450, 900)]
    between = [E("between_calls", 400, 450), E("between_calls", 900, 950)]
    ops = [E("op", 250, 300), E("op", 600, 700)]
    trace = tr.Trace(E("window", 0, 1000), sorted(calls + between),
                     [ops], [[]])
    records = [
        rec("sort", 110, 390, 0, -1,
            {"h2d_bytes": 10, "readbacks": 1, "d2h_bytes": 20}),
        rec("sort.encode", 120, 150, 0, 0),
        rec("sort.h2d", 150, 170, 0, 0),
        rec("sort.dispatch", 170, 200, 0, 0),
        rec("sort.readback", 240, 320, 0, 0),
        rec("sort.finish", 330, 380, 0, 0),
        rec("sort", 460, 890, 1, -1,
            {"h2d_bytes": 30, "readbacks": 3, "d2h_bytes": 40}),
        rec("sort.encode", 470, 520, 1, 6),
        rec("sort.readback", 590, 720, 1, 6),
        rec("sort.finish", 730, 800, 1, 6),
    ]
    return trace, records


class FakeRecorder:
    def __init__(self, records, dropped=0):
        self._records, self._dropped = records, dropped

    def records(self):
        return self._records

    def dropped(self):
        return self._dropped


@pytest.fixture
def run_with(monkeypatch):
    """A traced run whose program recorded ``records`` (None: a program
    without the recorder)."""
    def make(trace, records, dropped=0):
        monkeypatch.setattr(ps, "_recorder", lambda: (
            None if records is None else FakeRecorder(records, dropped)))
        return Run([], Window(start=0.0, end=1e-6), setup_s=1.0,
                   trace=trace)
    return make


def read(name, run):
    return load_module(BENCH / "metrics" / f"{name}.py").read(run)


def test_alignment_recovers_the_offset():
    calls = [E("sort_call", 0, 100), E("sort_call", 200, 260),
             E("sort_call", 300, 500)]
    # each root starts g after its call and ends g before its end; the
    # smallest g bounds the interval on both sides, so its midpoint is
    # the offset itself
    off, gaps = -7_000_123, (5, 2, 9)
    roots = [Record("sort", c.start + g - off, c.end - g - off, i, -1, {})
             for i, (c, g) in enumerate(zip(calls, gaps))]
    assert ps.align(calls, roots) == off


def test_alignment_fails_where_the_clocks_disagree():
    calls = [E("sort_call", 0, 100), E("sort_call", 200, 300)]
    fits = [Record("sort", 10, 90, 0, -1, {}),
            Record("sort", 210, 290, 1, -1, {})]
    # the second root is longer than its call by more than the slack
    long = fits[:1] + [Record("sort", 190, 310 + ps.SLACK_NS, 1, -1, {})]
    assert ps.align(calls, long) is None
    # by less than the slack, the midpoint of the (empty) interval stands
    near = fits[:1] + [Record("sort", 190, 300 + ps.SLACK_NS // 2, 1, -1,
                              {})]
    assert ps.align(calls, near) is not None
    with pytest.raises(RuntimeError, match="clocks disagree"):
        ps.build(long, calls, [])
    with pytest.raises(RuntimeError, match="1 'sort' spans for 2"):
        ps.build(fits[:1], calls, [])


def test_metrics_on_synthetic_intervals(run_with):
    trace, records = synthetic()
    run = run_with(trace, records)
    ms = 1e-6
    want = {
        "facade.encode_ms_per_req": (30 + 50) / 2 * ms,
        "facade.h2d_ms_per_req": 20 / 2 * ms,
        "facade.dispatch_ms_per_req": 30 / 2 * ms,
        "facade.finish_ms_per_req": (50 + 70) / 2 * ms,
        "facade.params_ms_per_req": 0.0,
        "facade.rank_to_perm_ms_per_req": 0.0,
        # readbacks of 80 and 130 ns, the device busy for 50 and 100
        "facade.readback_host_ms_per_req": (30 + 30) / 2 * ms,
        # calls of 300 and 450 ns, spans covering 210 and 250 of them (the
        # device busy only inside spans)
        "facade.unspanned_ms_per_req": (90 + 200) / 2 * ms,
        "facade.h2d_bytes_per_req": 20.0,
        "facade.d2h_bytes_per_req": 30.0,
        "facade.readbacks_per_req": 2.0,
        # idle 850 ns; spans and between_calls cover 560 of the window
        "device.idle_unattributed_share": 100 * (1000 - 560) / 850,
    }
    for name, value in want.items():
        assert read(name, run) == pytest.approx(value), name


def test_a_program_without_the_recorder_records_nothing(run_with):
    trace, _ = synthetic()
    run = run_with(trace, None)
    for name in ("facade.encode_ms_per_req", "facade.readback_host_ms_per_req",
                 "facade.h2d_bytes_per_req", "facade.readbacks_per_req"):
        assert read(name, run) == 0.0, name
    # everything the call spends off the device is unexplained
    assert read("facade.unspanned_ms_per_req", run) == pytest.approx(
        read("facade.host_ms_per_req", run))
    assert read("device.idle_unattributed_share", run) == pytest.approx(
        100 * (1000 - 150 - 100) / 850)


def test_an_untraced_run_reads_nothing_and_a_dropped_span_raises(run_with):
    trace, records = synthetic()
    run = run_with(None, records)
    assert read("facade.encode_ms_per_req", run) is None
    run = run_with(trace, records, dropped=1)
    with pytest.raises(RuntimeError, match="dropped 1"):
        read("facade.encode_ms_per_req", run)


def test_self_time_leaves_out_nested_spans():
    call = E("sort_call", 0, 100)
    records = [rec("sort", 10, 90, 0, -1), rec("sort.finish", 20, 80, 0, 0),
               rec("sort.readback", 30, 50, 0, 1)]
    p = ps.build(records, [call], [])
    got = {s.event.name: s.self_ns for s in p.calls[0].spans}
    assert got == {"sort.finish": 40, "sort.readback": 20}
    assert ps.unspanned_ms_per_call(p) == pytest.approx(40e-6)


def test_alignment_on_a_real_host_trace(tmp_path):
    """``sort()`` inside the harness's ``sort_call`` annotations under a
    profiler on the host: every root span fits its call once aligned."""
    from jax.profiler import TraceAnnotation

    from repro import sort as S
    from repro.runtime import spans
    x = np.random.default_rng(0).integers(0, 2**32, (2, 64),
                                          dtype=np.uint32)
    S.sort(x, engine="radix")
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with TraceAnnotation("sort_call"):
                S.sort(x, engine="radix")
    records = spans.records()
    spans.clear()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    calls = sorted(E(e.name, e.start_ns, e.end_ns)
                   for p in jax.profiler.ProfileData.from_file(path).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name == "sort_call")
    p = ps.build(records, calls, [])
    assert len(p.calls) == 5
    off = ps.align(calls, [r for r in records if r.parent < 0])
    for c, r in zip(calls, (r for r in records if r.parent < 0)):
        assert c.start - ps.SLACK_NS <= r.start_ns + off
        assert r.end_ns + off <= c.end + ps.SLACK_NS
    assert all(s.event.name.startswith("sort.") for c in p.calls
               for s in c.spans)
