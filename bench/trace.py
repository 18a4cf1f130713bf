"""From a JAX profiler trace to the intervals the per-layer metrics read.

The traced run records, on one clock:

* host spans that the harness writes with ``jax.profiler.TraceAnnotation``:
  ``window`` around the measured window; in a closed loop ``sort_call``
  around each call of ``sort()`` and ``between_calls`` around the
  harness's own work between two calls; in an open loop ``submit``,
  ``serve_step`` and ``serve_wait`` (``bench/harness.py``);
* device events on each ``/device:TPU:<i>`` plane: the ``XLA Modules``
  line (one event per executable run, named ``jit_<function>(<id>)``) and
  the ``XLA Ops`` line (one event per operation, named by its HLO text; a
  Pallas kernel is a ``custom-call`` with ``custom_call_target=
  "tpu_custom_call"``).

``reduce_profile`` keeps those events, clipped to the window, as plain
tuples; everything after it is arithmetic on intervals that the tests
check on synthetic events.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import NamedTuple

HOST_SPANS = ("window", "sort_call", "between_calls", "submit", "serve_step",
              "serve_wait")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


class Event(NamedTuple):
    name: str
    start: float   # ns on the trace's clock
    end: float


class Trace(NamedTuple):
    window: Event             # the harness's window span
    spans: list[Event]        # the harness's other spans in the window
    ops: list[list[Event]]    # per device: operations, clipped, by start
    modules: list[list[Event]]  # per device: executables, clipped, by start


def _clip(events, lo, hi) -> list[Event]:
    out = [Event(e.name, max(e.start, lo), min(e.end, hi))
           for e in events if e.end > lo and e.start < hi]
    return sorted(out, key=lambda e: e.start)


def reduce_profile(planes) -> Trace:
    """Planes of a ``jax.profiler.ProfileData`` (or anything shaped like
    them: ``.name``, ``.lines`` of ``.name`` and ``.events`` with
    ``.name``, ``.start_ns``, ``.end_ns``)."""
    spans, ops, modules = [], [], []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops.append([Event(e.name, e.start_ns, e.end_ns)
                        for e in (lines[OPS_LINE].events
                                  if OPS_LINE in lines else ())])
            modules.append([Event(e.name, e.start_ns, e.end_ns)
                            for e in (lines[MODULES_LINE].events
                                      if MODULES_LINE in lines else ())])
        elif plane.name.startswith("/host:"):
            spans += [Event(e.name, e.start_ns, e.end_ns)
                      for ln in plane.lines for e in ln.events
                      if e.name in HOST_SPANS]
    windows = [s for s in spans if s.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    if not ops:
        raise ValueError("the trace holds no TPU device plane")
    w = windows[0]
    return Trace(w, _clip([s for s in spans if s.name != "window"],
                          w.start, w.end),
                 [_clip(d, w.start, w.end) for d in ops],
                 [_clip(d, w.start, w.end) for d in modules])


def union(events) -> list[tuple[float, float]]:
    """Disjoint (start, end) intervals covering the events."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace) -> float:
    """Nanoseconds in which an operation ran, averaged over the devices."""
    per = [sum(b - a for a, b in union(d)) for d in trace.ops]
    return sum(per) / len(per)


def window_ns(trace: Trace) -> float:
    return trace.window.end - trace.window.start


def overlap_ns(busy: list[tuple[float, float]], starts: list[float],
               lo: float, hi: float) -> float:
    """Length of the part of [lo, hi) that the disjoint, sorted intervals
    ``busy`` (whose starts are ``starts``) cover."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    total = 0.0
    for a, b in busy[i:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def idle_gaps(trace: Trace, device: int = 0) -> list[tuple[str, float]]:
    """Every stretch of the window in which no operation ran on ``device``,
    as (name of the host span it fell in, seconds), longest first.  A gap
    is named by the host span that holds its midpoint."""
    busy = union(trace.ops[device])
    edges = ([trace.window.start] + [x for iv in busy for x in iv]
             + [trace.window.end])
    starts = [s.start for s in trace.spans]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        j = bisect.bisect_right(starts, mid) - 1
        name = (trace.spans[j].name
                if j >= 0 and trace.spans[j].end >= mid else "outside_spans")
        gaps.append((name, (b - a) / 1e9))
    return sorted(gaps, key=lambda g: -g[1])


def host_minus_device_ns(trace: Trace, span: str = "sort_call",
                         device: int = 0) -> list[float]:
    """For each host span of that name: its length less the device-busy
    time inside it."""
    busy = union(trace.ops[device])
    starts = [a for a, _ in busy]
    return [(s.end - s.start) - overlap_ns(busy, starts, s.start, s.end)
            for s in trace.spans if s.name == span]


def count_spans(trace: Trace, span: str = "sort_call") -> int:
    return sum(1 for s in trace.spans if s.name == span)


def module_base(name: str) -> str:
    """``jit_radix_sort_keys(1234)`` -> ``jit_radix_sort_keys``."""
    return name.split("(", 1)[0]


def op_base(name: str) -> str:
    """``%fusion.20 = s32[...] fusion(...)`` -> ``fusion.20``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def ops_by_module(trace: Trace, device: int = 0):
    """(module base name, op event) for each operation, the module being
    the executable whose run holds the operation's start."""
    mods = trace.modules[device]
    starts = [m.start for m in mods]
    for op in trace.ops[device]:
        j = bisect.bisect_right(starts, op.start) - 1
        inside = j >= 0 and mods[j].end >= op.start
        yield (module_base(mods[j].name) if inside else ""), op


def module_ns(trace: Trace, base: str) -> float:
    """Device nanoseconds of the runs of executables named ``base``,
    summed over the devices."""
    return sum(m.end - m.start for d in trace.modules for m in d
               if module_base(m.name) == base)


def kernel_ns(trace: Trace, module: str, marker: str) -> float:
    """Device nanoseconds of the operations inside runs of ``module`` whose
    HLO text holds ``marker``, summed over the devices."""
    return sum(op.end - op.start for dev in range(len(trace.ops))
               for mod, op in ops_by_module(trace, dev)
               if mod == module and marker in op.name)


def top_ops(trace: Trace, n: int = 10) -> list[tuple[str, float]]:
    """The ``n`` operations that took most device time, as
    (``module/op``, seconds summed over their runs and the devices)."""
    total: dict[str, float] = defaultdict(float)
    for dev in range(len(trace.ops)):
        for mod, op in ops_by_module(trace, dev):
            total[f"{mod}/{op_base(op.name)}"] += (op.end - op.start) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
