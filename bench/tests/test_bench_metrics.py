"""The end-to-end arithmetic, the roofline functions and the peak table,
and every metric reader on a recorded trace."""
from __future__ import annotations

import numpy as np
import pytest

from bench import roofline
from bench.harness import Call, Run, Window
from bench.spec import BENCH, load_module
from bench.tests.test_bench_trace import recorded
from bench.traffic.generate import Request


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py")


def make_run(latencies, errors=(), shape=(2, 8), stop_after=None,
             start=100.0, end=102.0, trace=None):
    pool = [Request(np.zeros(shape, np.uint16), stop_after, "random")]
    calls = [Call(0, t, object() if i not in errors else None,
                  "Traceback" if i in errors else None)
             for i, t in enumerate(latencies)]
    w = Window(start=start, end=end, calls=calls, compiles=2)
    return Run(pool, w, setup_s=7.5, trace=trace,
               peaks=roofline.peaks("TPU v5 lite"))


def test_p95_is_taken_over_every_completed_call():
    lat = list(np.linspace(0.001, 0.100, 100))
    run = make_run(lat)
    assert reader("p95_ms").read(run) == pytest.approx(
        np.percentile(lat, 95) * 1e3)
    # a call that raised did not complete and has no latency
    run = make_run(lat + [9.0], errors=(100,))
    assert reader("p95_ms").read(run) == pytest.approx(
        np.percentile(lat, 95) * 1e3)


def test_rate_is_all_completed_elements_over_the_whole_window():
    run = make_run([0.1] * 10, errors=(3,), shape=(4, 256))
    # nine calls returned, 1024 elements each, over 2 s of window
    assert reader("elems_per_s").read(run) == pytest.approx(9 * 1024 / 2.0)
    assert reader("setup_s").read(run) == 7.5
    assert reader("facade.compiles_in_window").read(run) == 2


def test_problem_bytes_and_share():
    # top-m: keys in, m int32 indices out per array
    assert roofline.problem_bytes(64, 1024, 2, 32) == (64 * 1024 * 2
                                                       + 64 * 32 * 4)
    assert roofline.problem_bytes(64, 1024, 2, 5000) == 64 * 1024 * 6
    # full sort: keys in, one int32 index per key out
    assert roofline.problem_bytes(1, 65536, 4, None) == 65536 * 8
    # 819 bytes at 819 GB/s take 1 ns; in 4 ns that is 25%
    assert roofline.share_pct(819, 819e9, 4e-9) == pytest.approx(25.0)


def test_peaks_are_looked_up_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,cell_metrics", [
    ("topm_u16.m32", ("device.idle_share", "facade.host_ms_per_req",
                      "fused_tns.device_ms_per_req", "fused_tns_roofline")),
    ("fullsort_u32.full", ("device.idle_share", "facade.host_ms_per_req",
                           "radix.device_ms_per_req", "radix_roofline")),
])
def test_readers_on_recorded_traces(name, cell_metrics):
    t = recorded(name)
    shape, m = ((64, 1024), 32) if name.startswith("topm") else ((1, 65536),
                                                                 None)
    dtype = np.uint16 if name.startswith("topm") else np.uint32
    run = make_run([0.01] * 3)
    run.pool[0] = Request(np.zeros(shape, dtype), m, "random")
    run.window.calls[:] = run.window.calls[:len(t.spans) // 2]
    run.trace = t
    for metric in cell_metrics:
        value = reader(metric).read(run)
        assert value is not None and value > 0, metric
        if metric.endswith(("_roofline", "idle_share")):
            assert value <= 100.0, metric
    # the other engine's readers find nothing, and say so
    other = (("radix.device_ms_per_req", "radix_roofline")
             if name.startswith("topm") else
             ("fused_tns.device_ms_per_req", "fused_tns_roofline"))
    for metric in other:
        assert reader(metric).read(run) is None


def test_device_readers_read_nothing_without_a_trace():
    run = make_run([0.01])
    for metric in ("device.idle_share", "facade.host_ms_per_req",
                   "fused_tns.device_ms_per_req", "fused_tns_roofline",
                   "radix.device_ms_per_req", "radix_roofline"):
        assert reader(metric).read(run) is None
