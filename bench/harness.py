"""One run of one cell: set-up, the measured window, the check, the line.

A cell names, in its data files (``bench/spec.py`` finds each by name):

* its entry, the system under test: a module of ``bench/entries/`` whose
  ``make(cfg)`` builds it;
* its generator: a module of ``bench/traffic/`` whose ``make_pool(cfg,
  traffic, seed)`` makes the requests from the seed;
* its loop, ``traffic["loop"]``:

  ``closed``, one caller (``callers`` 1): the entry is a synchronous call
  ``call(x, stop_after)``, issued once the previous one has returned.  A
  call's latency is its own wall time, from the call to its return with
  host arrays.  The window cycles through the pool for the given seconds
  and ends when the call under way at that moment returns.  Host spans:
  ``sort_call`` around each call, ``between_calls`` around the harness's
  work between two.

  ``open``: requests are due on a schedule made from the seed
  (``bench/traffic/arrivals.py``), whatever the system does; the entry is
  a service with ``submit(rid, request)``, ``step()`` (one unit of the
  system's own work, returning the requests that reached an end as
  ``(rid, result, status)``: status None with an answer, else its name)
  and ``busy()``.  The harness submits each request when it is due, or as
  soon after as the host gets to it, calls ``step()`` while the entry is
  busy and otherwise sleeps until the next due time.  The schedule ends
  at the given seconds; the loop then drains for at most
  ``traffic["drain_s"]``.  A request's latency runs from its due time to
  the host clock at the ``step()`` that returned it, so a stall counts
  the wait it imposes on every request due during it; how late the host
  submitted (lateness) is reported beside it.  The window runs from the
  schedule's start until every request has come back or the drain has
  ended, and never ends before the given seconds, so a service that stops
  answering lowers the rate.  A request rejected, expired, failed or
  unfinished at the drain's end counts in ``failed`` and not against
  ``correct``, which is about the answers that came back; its latency
  runs from its due time to the window's end, so it counts in the tail.
  Host spans: ``submit``, ``serve_step`` around each step, ``serve_wait``
  around each sleep.

In both loops ``window`` spans the whole, set-up runs each distinct
request shape of the pool through the entry once, compiles are counted
in the window, nothing is generated or compared inside it, and every
answer is compared with the cell's reference after it.  A later cell adds
files and entries; this module does not change.
"""
from __future__ import annotations

import glob
import shutil
import sys
import tempfile
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import NamedTuple

import numpy as np

from bench import roofline
from bench import trace as tr
from bench.spec import Cell
from bench.traffic import arrivals

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
    latency_s: float          # open loop: censored where no answer came
    result: object            # None where none came
    error: str | None         # the traceback, where the call raised
    status: str | None = None  # open loop: how a request without an
    #                            answer ended (rejected, expired, failed,
    #                            unfinished)


@dataclass
class Window:
    start: float                     # host clock, seconds
    end: float = 0.0
    calls: list[Call] = field(default_factory=list)
    compiles: int = 0
    lateness: list[float] | None = None   # open loop: submit - due, s


@dataclass
class Run:
    """What the metric readers read."""
    pool: list
    window: Window
    setup_s: float
    trace: tr.Trace | None = None
    peaks: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window.end - self.window.start

    @property
    def done(self) -> list[Call]:
        """The calls that returned an answer."""
        return [c for c in self.window.calls
                if c.error is None and c.status is None]

    @property
    def latencies(self) -> list[float]:
        """Closed loop: each answered call's.  Open loop: every request's,
        one without an answer censored at the window's end."""
        if self.window.lateness is None:
            return [c.latency_s for c in self.done]
        return [c.latency_s for c in self.window.calls]

    @property
    def answers(self) -> list[tuple[int, object]]:
        return [(c.pool_index, c.result) for c in self.done]

    @property
    def elems(self) -> int:
        """Input elements of the calls that returned."""
        return sum(self.pool[c.pool_index].x.size for c in self.done)


class Synchronous:
    """A closed-loop call behind the open loop's interface: each step
    answers the oldest submitted request (the control and the tests put
    their calls in the program's place with it)."""

    def __init__(self, call):
        self.call, self.todo = call, deque()

    def submit(self, rid: int, request) -> None:
        self.todo.append((rid, request))

    def busy(self) -> bool:
        return bool(self.todo)

    def step(self):
        rid, req = self.todo.popleft()
        return [(rid, self.call(req.x, req.stop_after), None)]


def run_window(call, pool: list, seconds: float,
               counter: CompileCounter) -> Window:
    """Closed loop: call through the pool in order until ``seconds`` have
    passed; the window ends when the call under way at that moment
    returns."""
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


def run_open(service, pool: list, due: np.ndarray, drain_s: float,
             seconds: float, counter: CompileCounter) -> Window:
    """Open loop: request ``rid`` (pool entry ``rid % len(pool)``) is due
    ``due[rid]`` seconds after the start.  The loop stops once every
    request has come back, or ``drain_s`` after ``seconds``; an exception
    from the service stops it at once, every request still out charged
    with it.  The window ends when the loop stops, never before
    ``seconds``.  A request that got no answer is given the latency from
    its due time to the window's end: censored there, it counts in the
    tail as the least it would have waited."""
    from jax.profiler import TraceAnnotation
    counter.count, counter.on = 0, True
    calls: list[Call | None] = [None] * len(due)
    lateness, error = [], None
    with TraceAnnotation("window"):
        w = Window(start=time.perf_counter(), lateness=lateness)
        at = w.start + due
        close = w.start + seconds + drain_s
        i = 0
        try:
            while True:
                now = time.perf_counter()
                if i < len(at) and at[i] <= now:
                    with TraceAnnotation("submit"):
                        while i < len(at) and at[i] <= now:
                            service.submit(i, pool[i % len(pool)])
                            lateness.append(time.perf_counter() - at[i])
                            i += 1
                if now >= close:
                    break
                if service.busy():
                    with TraceAnnotation("serve_step"):
                        out = service.step()
                    t = time.perf_counter()
                    for rid, res, status in out:
                        calls[rid] = Call(rid % len(pool), t - at[rid], res,
                                          None, status)
                elif i < len(at):
                    with TraceAnnotation("serve_wait"):
                        time.sleep(max(0.0, at[i] - time.perf_counter()))
                else:
                    break
        except Exception:   # the service failed: charged, not fatal
            error = traceback.format_exc()
        w.end = max(time.perf_counter(), w.start + seconds)
    counter.on = False
    w.compiles = counter.count
    w.calls = [
        c._replace(latency_s=w.end - at[rid]) if c and c.status
        else c or Call(rid % len(pool), w.end - at[rid], None, error,
                       None if error else "unfinished")
        for rid, c in enumerate(calls)]
    return w


def warm_up(entry, pool: list, loop: str) -> None:
    """Run each distinct request shape of the pool through the entry (a
    call, or an open loop's service) once, in pool order, printing the
    time each took."""
    if loop == "closed":
        for m in dict.fromkeys(r.stop_after for r in pool):
            # each stop_after is its own executable
            req = next(r for r in pool if r.stop_after == m)
            t = time.perf_counter()
            entry(req.x, m)
            print(f"warm-up stop_after={m}: {time.perf_counter() - t:.3f} s",
                  file=sys.stderr, flush=True)
        return
    shapes: dict[tuple, object] = {}
    for r in pool:
        shapes.setdefault((r.x.shape, r.x.dtype.str, r.stop_after), r)
    for rid, ((shape, dtype, m), req) in enumerate(shapes.items()):
        t = time.perf_counter()
        entry.submit(-1 - rid, req)
        ends = []
        while entry.busy():
            ends += entry.step()
        print(f"warm-up {dtype}{shape} stop_after={m}: "
              f"{time.perf_counter() - t:.3f} s, "
              f"{[s or 'done' for _, _, s in ends]}",
              file=sys.stderr, flush=True)


def _read_trace(trace_dir: str) -> tr.Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    return tr.reduce_profile(ProfileData.from_file(paths[0]).planes)


def _ms(values) -> str:
    v = np.asarray(values) * 1e3
    if not v.size:
        return "none"
    return (f"p50 {np.median(v):.3f}, p95 {np.percentile(v, 95):.3f}, "
            f"max {v.max():.3f}")


def _summary(window: Window, window_s: float) -> str:
    lat = np.array([c.latency_s for c in window.calls
                    if c.error is None and c.status is None]) * 1e3
    thirds = [f"{t.mean():.3f}" for t in np.array_split(lat, 3) if t.size]
    head = (f"window: {len(window.calls)} calls in {window_s:.3f} s, "
            f"{window.compiles} compiles; "
            f"latency ms p50 {np.median(lat):.3f}, mean {lat.mean():.3f}, "
            f"max {lat.max():.3f}; mean by thirds {', '.join(thirds)}"
            if lat.size else f"window: {len(window.calls)} calls, none "
            f"answered, {window.compiles} compiles")
    if window.lateness is None:
        return head
    ends: dict[str, int] = {}
    for c in window.calls:
        end = "raised" if c.error else c.status or "answered"
        ends[end] = ends.get(end, 0) + 1
    return (f"{head}\nopen loop: {ends}; latency from due; generator "
            f"lateness ms {_ms(window.lateness)} over "
            f"{len(window.lateness)} submits")


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            t_start: float, call=None, pool=None) -> tuple[Run, dict]:
    """Set up, run the window, check every answer.  ``t_start`` is the
    host clock at process start; ``call`` replaces the cell's entry with a
    closed-loop call (the control and the tests of the check use it; an
    open loop drives it through ``Synchronous``); ``pool`` waits for a
    pool made in the background (``pool_in_background``).  Returns the
    run and the line."""
    import jax
    loop = cell.traffic["loop"]
    if loop not in ("closed", "open") or (
            loop == "closed" and cell.traffic["callers"] != 1):
        raise ValueError("the harness drives one caller in a closed loop "
                         f"or an open loop; traffic asks for {loop!r} with "
                         f"{cell.traffic.get('callers')} callers")
    device = jax.devices()[0]
    peaks = roofline.peaks(device.device_kind) if traced else None
    t0 = time.perf_counter()
    pool = (pool() if pool
            else cell.generator.make_pool(cell.cfg, cell.traffic, seed))
    if loop == "closed":
        entry = call or cell.entry.make(cell.cfg)
    else:
        entry = Synchronous(call) if call else cell.entry.make(cell.cfg)
        due = arrivals.schedule(cell.traffic, seed, seconds).due
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    t1 = time.perf_counter()
    warm_up(entry, pool, loop)
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
        if loop == "closed":
            window = run_window(entry, pool, seconds, counter)
        else:
            window = run_open(entry, pool, due, cell.traffic["drain_s"],
                              seconds, counter)
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
    print(_summary(window, run.window_s), file=sys.stderr, flush=True)
    errors = [c.error for c in window.calls if c.error]
    for e in dict.fromkeys(errors[:3]):
        print(e, file=sys.stderr)
    unanswered = sum(1 for c in window.calls if c.status is not None)
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
            "failed": len(errors) + wrong_calls + unanswered,
            "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = tr.busy_ns(run.trace) / 1e9
        dev["window_s"] = tr.window_ns(run.trace) / 1e9
        line["breakdown"] = {"device_ops": tr.top_ops(run.trace, 10),
                             "idle_gaps": tr.idle_gaps(run.trace)[:10]}
    line["checks"] = checks
    return run, line
