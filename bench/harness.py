"""One run of one cell: set-up, the measured window, the check, the line.

The loop is closed with one caller: ``sort()`` is a synchronous library
call, and each call is issued once the previous one has returned its
host arrays.  Set-up makes the request pool from the seed and calls each
shape the window will use once; the window then cycles through the pool
for the given seconds.  Nothing is generated or compared inside it.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench import roofline
from bench import trace as tr
from bench.spec import Cell
from bench.traffic.generate import Request, make_pool

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compiles while ``on``: a listener on JAX's
    monitoring events, as ``chip_smoke.py`` has it."""

    def __init__(self):
        self.count, self.on = 0, False

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event == COMPILE_EVENT:
            self.count += 1


class Call(NamedTuple):
    pool_index: int
    latency_s: float
    result: object        # None where the call raised
    error: str | None     # its traceback


@dataclass
class Window:
    start: float                     # host clock, seconds
    end: float = 0.0
    calls: list[Call] = field(default_factory=list)
    compiles: int = 0


@dataclass
class Run:
    """What the metric readers read."""
    pool: list[Request]
    window: Window
    setup_s: float
    trace: tr.Trace | None = None
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window.end - self.window.start

    @property
    def done(self) -> list[Call]:
        """The calls that returned."""
        return [c for c in self.window.calls if c.error is None]

    @property
    def answers(self) -> list[tuple[int, object]]:
        return [(c.pool_index, c.result) for c in self.done]

    @property
    def elems(self) -> int:
        """Input elements of the calls that returned."""
        return sum(self.pool[c.pool_index].x.size for c in self.done)


def sort_call(cfg: dict):
    """The system under test: ``repro.sort.sort`` as a user calls it."""
    from repro import sort
    kw = dict(engine=cfg["engine"], fmt=cfg["fmt"], width=cfg["width"],
              k=cfg["k"], ascending=cfg["ascending"])

    def call(x: np.ndarray, stop_after: int | None):
        return sort.sort(x, stop_after=stop_after, **kw)
    return call


def run_window(call, pool: list[Request], seconds: float,
               counter: CompileCounter) -> Window:
    """Call through the pool in order until ``seconds`` have passed; the
    window ends when the call under way at that moment returns."""
    from jax.profiler import TraceAnnotation
    counter.count, counter.on = 0, True
    with TraceAnnotation("window"):
        w = Window(start=time.perf_counter())
        deadline = w.start + seconds
        i = 0
        while True:
            j = i % len(pool)
            req = pool[j]
            with TraceAnnotation("sort_call"):
                t0 = time.perf_counter()
                try:
                    res, err = call(req.x, req.stop_after), None
                except Exception:   # a failed call is counted, not fatal
                    res, err = None, traceback.format_exc()
                t1 = time.perf_counter()
            with TraceAnnotation("between_calls"):
                w.calls.append(Call(j, t1 - t0, res, err))
                i += 1
                if t1 >= deadline:
                    w.end = t1
                    break
    counter.on = False
    w.compiles = counter.count
    return w


def _read_trace(trace_dir: str) -> tr.Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    return tr.reduce_profile(ProfileData.from_file(paths[0]).planes)


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            t_start: float, call=None, pool=None) -> tuple[Run, dict]:
    """Set up, run the window, check every answer.  ``t_start`` is the
    host clock at process start; ``call`` replaces ``sort()`` (the control
    and the tests of the check use it); ``pool`` waits for a pool made in
    the background (``pool_in_background``).  Returns the run and the
    line."""
    import jax
    if cell.traffic["loop"] != "closed" or cell.traffic["callers"] != 1:
        raise ValueError("the harness drives one caller in a closed loop; "
                         f"traffic asks for {cell.traffic['callers']} in a "
                         f"{cell.traffic['loop']} loop")
    device = jax.devices()[0]
    peaks = roofline.peaks(device.device_kind) if traced else None
    t0 = time.perf_counter()
    pool = pool() if pool else make_pool(cell.cfg, cell.traffic, seed)
    call = call or sort_call(cell.cfg)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    t1 = time.perf_counter()
    for m in dict.fromkeys(r.stop_after for r in pool):
        # warm-up: each stop_after is its own executable
        req = next(r for r in pool if r.stop_after == m)
        call(req.x, m)
    print(f"set-up: {t0 - t_start:.3f} s to JAX and the devices, pool "
          f"{t1 - t0:.3f} s more, warm-up {time.perf_counter() - t1:.3f} s",
          file=sys.stderr, flush=True)
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1      # the harness's own spans
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window = run_window(call, pool, seconds, counter)
    finally:
        if traced:
            jax.profiler.stop_trace()
    run = Run(pool, window, window.start - t_start, peaks=peaks)
    if traced:
        t0 = time.perf_counter()
        try:
            run.trace = _read_trace(trace_dir)
            nbytes = sum(p.stat().st_size
                         for p in Path(trace_dir).rglob("*") if p.is_file())
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: {nbytes} bytes, read in "
              f"{time.perf_counter() - t0:.3f} s, "
              f"{sum(map(len, run.trace.ops))} device operations",
              file=sys.stderr, flush=True)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    lat = np.array([c.latency_s for c in window.calls]) * 1e3
    thirds = [f"{t.mean():.3f}" for t in np.array_split(lat, 3)]
    print(f"window: {len(lat)} calls in {run.window_s:.3f} s; latency ms "
          f"p50 {np.median(lat):.3f}, mean {lat.mean():.3f}, max "
          f"{lat.max():.3f}; mean by thirds {', '.join(thirds)}",
          file=sys.stderr, flush=True)
    errors = [c.error for c in window.calls if c.error]
    for e in errors[:3]:
        print(e, file=sys.stderr)
    compared, wrong_calls = cell.reference.compare(
        cell.cfg, pool, run.answers, np.random.default_rng(seed))
    checks = {"calls_raised": {"value": len(errors), "limit": 0}, **compared}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = m.reader.read(run)
        if value is None:
            # a metric listed for this cell that finds nothing is a fault
            # of the run, unless the run has already failed its check
            if correct and (m.required or not traced):
                raise RuntimeError(f"metric {m.name} found nothing to read "
                                   f"in cell {cell.name}")
            continue
        metrics[m.name] = {"value": value, "unit": m.unit}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    line = {"correct": correct,
            "attempted": len(window.calls),
            "failed": len(errors) + wrong_calls,
            "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = tr.busy_ns(run.trace) / 1e9
        dev["window_s"] = tr.window_ns(run.trace) / 1e9
        line["breakdown"] = {"device_ops": tr.top_ops(run.trace, 10),
                             "idle_gaps": tr.idle_gaps(run.trace)[:10]}
    line["checks"] = checks
    return run, line
