"""The reduction from a profiler trace to intervals, on synthetic events
and on two small traces recorded on a TPU v5e (one call path each)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def synthetic():
    """Window 0-100 ns: two calls, device busy in each."""
    host = plane("/host:CPU", main=[
        ev("window", 0, 100),
        ev("sort_call", 0, 40), ev("between_calls", 40, 50),
        ev("sort_call", 50, 95), ev("between_calls", 95, 100),
        ev("not_ours", 10, 20)])
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_f(1)", 10, 30), ev("jit_g(2)", 60, 80),
                             ev("jit_f(1)", 90, 120)],
                XLA_Ops=[ev("%a.1 = s32[8] fusion(x)", 10, 20),
                         ev("%k.2 = s32[8] custom-call(y), "
                            'custom_call_target="tpu_custom_call"', 15, 30),
                         ev("%a.1 = s32[8] fusion(x)", 60, 80),
                         ev("%a.1 = s32[8] fusion(x)", 90, 120)])
    other = plane("#Chip0 Misc", Steps=[ev("step", 0, 100)])
    return tr.reduce_profile([host, dev, other])


def test_reduce_keeps_harness_spans_and_clips_device_events():
    t = synthetic()
    assert t.window == tr.Event("window", 0, 100)
    assert [s.name for s in t.spans] == ["sort_call", "between_calls"] * 2
    assert t.ops[0][-1] == tr.Event("%a.1 = s32[8] fusion(x)", 90, 100)
    assert t.modules[0][-1].end == 100


def test_busy_is_the_union_of_operations():
    t = synthetic()
    # [10, 30) + [60, 80) + [90, 100): overlapping ops count once
    assert tr.union(t.ops[0]) == [(10, 30), (60, 80), (90, 100)]
    assert tr.busy_ns(t) == 50
    assert tr.window_ns(t) == 100


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    gaps = tr.idle_gaps(synthetic())
    # 0-10 in the first call; 30-60, centred between the calls; 80-90 in
    # the second call
    assert gaps == [("between_calls", pytest.approx(30e-9)),
                    ("sort_call", pytest.approx(10e-9)),
                    ("sort_call", pytest.approx(10e-9))]


def test_host_time_is_the_span_less_device_time_inside_it():
    assert tr.host_minus_device_ns(synthetic()) == [40 - 20, 45 - (20 + 5)]


def test_kernel_events_are_found_by_module_and_marker():
    t = synthetic()
    assert tr.kernel_ns(t, "jit_f", "tpu_custom_call") == 15
    assert tr.kernel_ns(t, "jit_g", "tpu_custom_call") == 0
    assert tr.module_ns(t, "jit_f") == 20 + 10
    assert tr.count_spans(t) == 2
    top = dict(tr.top_ops(t))
    assert top == pytest.approx({"jit_f/a.1": 20e-9, "jit_f/k.2": 15e-9,
                                 "jit_g/a.1": 20e-9})


def test_a_trace_without_its_window_or_device_is_refused():
    host = plane("/host:CPU", main=[ev("sort_call", 0, 1)])
    dev = plane("/device:TPU:0", XLA_Ops=[])
    with pytest.raises(ValueError, match="window"):
        tr.reduce_profile([host, dev])
    host = plane("/host:CPU", main=[ev("window", 0, 1)])
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce_profile([host])


def recorded(name):
    from jax.profiler import ProfileData
    return tr.reduce_profile(
        ProfileData.from_file(str(DATA / f"{name}.xplane.pb")).planes)


def test_recorded_fused_tns_trace():
    t = recorded("topm_u16.m32")        # three calls, 64 x 1024, m = 32
    assert tr.count_spans(t) == 3
    kernel = tr.kernel_ns(t, "jit__fused_tns_rank", "tpu_custom_call")
    assert 0 < kernel < tr.busy_ns(t) < tr.window_ns(t)
    assert tr.module_ns(t, "jit_radix_sort_keys") == 0
    assert tr.top_ops(t, 1)[0][0] == "jit__fused_tns_rank/_fused_tns_rank.1"


def test_recorded_radix_trace():
    t = recorded("fullsort_u32.full")   # one call, 65,536 keys
    assert tr.count_spans(t) == 1
    radix = tr.module_ns(t, "jit_radix_sort_keys")
    assert 0 < tr.busy_ns(t) <= radix < tr.window_ns(t)
    assert tr.kernel_ns(t, "jit__fused_tns_rank", "tpu_custom_call") == 0
