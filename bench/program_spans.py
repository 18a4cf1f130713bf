"""The program's own spans and counters (``repro.runtime.spans``) put on
the device trace's clock, for the per-layer metrics of the facade.

While the traced window runs, ``sort()`` keeps one root ``sort`` span per
call, its child spans (``sort.encode``, ``sort.h2d``, ``sort.dispatch``,
``sort.readback``, ...) and its counters, on the host's
``perf_counter_ns`` clock; the trace has a clock of its own.  Each root
span lies inside the harness's ``sort_call`` span of the same call, so the
offset between the two clocks lies in [max over calls of (call start -
root start), min over calls of (call end - root end)].  The midpoint of
that interval is taken; an interval empty by more than ``SLACK_NS`` means
the clocks disagree.

Every metric is taken per traced call (the harness's ``sort_call``
spans), so a program without the recorder reads as one that records
nothing: its spans and counters read 0, its unspanned time is the whole
host time of the call, and every device-idle stretch outside
``between_calls`` is unattributed.  (The harness requires a listed metric
to read a number.)  A program that has the recorder, but whose root spans
do not match the traced calls one for one or cannot be aligned, is a fault
of the measurement and raises, so that the traced run fails loudly.
"""
from __future__ import annotations

import bisect
from typing import NamedTuple

from bench import trace as tr

ROOT, CALL, BETWEEN = "sort", "sort_call", "between_calls"
SLACK_NS = 20_000


class Span(NamedTuple):
    event: tr.Event    # on the trace's clock
    self_ns: float     # its length less the part its own child spans cover


class ProgramCall(NamedTuple):
    call: tr.Event     # the harness's sort_call span
    spans: list[Span]  # every span under the program's root span
    counts: dict


class Program(NamedTuple):
    calls: list[ProgramCall]
    busy: list[tuple[float, float]]   # device 0's busy intervals
    starts: list[float]               # their starts


def covered_ns(events) -> float:
    """Length of the union of the events."""
    return sum(b - a for a, b in tr.union(events))


def align(calls: list[tr.Event], roots) -> float | None:
    """The offset (trace clock less recorder clock, ns) that puts every
    root record (``start_ns``, ``end_ns``) inside its call's span, or None
    where no offset comes within ``SLACK_NS`` of doing so."""
    lo = max(c.start - r.start_ns for c, r in zip(calls, roots))
    hi = min(c.end - r.end_ns for c, r in zip(calls, roots))
    if lo > hi + SLACK_NS:
        return None
    return (lo + hi) / 2


def build(records, calls: list[tr.Event], busy) -> Program:
    """The traced calls with the recorded spans on the trace's clock."""
    starts = [a for a, _ in busy]
    if records is None:
        return Program([ProgramCall(c, [], {}) for c in calls], busy, starts)
    roots = [i for i, r in enumerate(records)
             if r.parent < 0 and r.name == ROOT]
    if len(roots) != len(calls):
        raise RuntimeError(f"the program recorded {len(roots)} '{ROOT}' "
                           f"spans for {len(calls)} traced calls")
    offset = align(calls, [records[i] for i in roots])
    if offset is None:
        raise RuntimeError("the program's spans do not fit inside the "
                           "traced calls: the clocks disagree")
    ev = [tr.Event(r.name, r.start_ns + offset, r.end_ns + offset)
          for r in records]
    children: dict[int, list[int]] = {}
    for i, r in enumerate(records):
        if r.parent >= 0:
            children.setdefault(r.parent, []).append(i)
    out = []
    for c, i in zip(calls, roots):
        spans, todo = [], list(children.get(i, ()))
        while todo:
            j = todo.pop()
            kids = children.get(j, [])
            todo += kids
            e = ev[j]
            spans.append(Span(e, (e.end - e.start)
                              - covered_ns(ev[k] for k in kids)))
        spans.sort(key=lambda s: s.event.start)
        out.append(ProgramCall(c, spans, records[i].counts or {}))
    return Program(out, busy, starts)


def _recorder():
    """``repro.runtime.spans``, or None where the program has none."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def program(run) -> Program | None:
    """The traced run's calls with the program's spans; None for an
    untraced run or one with no call."""
    if run.trace is None:
        return None
    spans, records = _recorder(), None
    if spans is not None:
        if spans.dropped():
            raise RuntimeError(f"the program dropped {spans.dropped()} spans")
        records = spans.records()
    calls = [s for s in run.trace.spans if s.name == CALL]
    if not calls:
        return None
    return build(records, calls, tr.union(run.trace.ops[0]))


def busy_in(p: Program, lo: float, hi: float) -> list[tr.Event]:
    """The parts of the device's busy intervals inside [lo, hi)."""
    i = max(bisect.bisect_right(p.starts, lo) - 1, 0)
    out = []
    for a, b in p.busy[i:]:
        if a >= hi:
            break
        if b > lo:
            out.append(tr.Event("busy", max(a, lo), min(b, hi)))
    return out


def span_ms_per_call(p: Program, name: str) -> float:
    """Mean self time of the spans named ``name`` per call."""
    return sum(s.self_ns for c in p.calls for s in c.spans
               if s.event.name == name) / len(p.calls) / 1e6


def host_ms_per_call(p: Program, name: str) -> float:
    """Mean time per call in spans named ``name`` during which the device
    ran nothing."""
    total = 0.0
    for c in p.calls:
        for s in c.spans:
            if s.event.name == name:
                e = s.event
                total += (e.end - e.start) - covered_ns(
                    busy_in(p, e.start, e.end))
    return total / len(p.calls) / 1e6


def unspanned_ms_per_call(p: Program) -> float:
    """Mean time per call covered neither by a span of the program nor by
    device-busy time."""
    total = 0.0
    for c in p.calls:
        r = c.call
        total += (r.end - r.start) - covered_ns(
            [s.event for s in c.spans] + busy_in(p, r.start, r.end))
    return total / len(p.calls) / 1e6


def count_per_call(p: Program, name: str) -> float:
    return sum(c.counts.get(name, 0) for c in p.calls) / len(p.calls)


def idle_unattributed_pct(p: Program, trace: tr.Trace) -> float | None:
    """Share of the window's device-idle time during which the host was in
    no child span of the program and not between calls, in %."""
    w = trace.window
    busy = [tr.Event("busy", a, b) for a, b in p.busy]
    idle = (w.end - w.start) - covered_ns(busy)
    if idle <= 0:
        return None
    host = ([s.event for c in p.calls for s in c.spans]
            + [s for s in trace.spans if s.name == BETWEEN])
    host = [tr.Event(e.name, max(e.start, w.start), min(e.end, w.end))
            for e in host if e.end > w.start and e.start < w.end]
    covered = covered_ns(busy + host)
    return 100.0 * ((w.end - w.start) - covered) / idle
