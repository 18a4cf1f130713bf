"""Host spans and counters of the ``sort()`` path.

``span(name)`` is a ``jax.profiler.TraceAnnotation``, so every span shows
in a profiler trace (Perfetto, TensorBoard) on the same clock as the device
planes.  While a profiler session records host events
(``TraceAnnotation.is_enabled()``), the span is also kept in a bounded
in-memory buffer: its name, ``time.perf_counter_ns()`` at start and end,
the call it belongs to (the sequence number of the outermost, root span)
and the index of its parent.  ``count(name, n)`` adds to the counters of
the open root span, again only while recording.  With no profiler running
nothing is kept and no annotation is made: a span then costs one check.

Spans belong in host code only: inside a jitted function a span would time
tracing, not running.

The ``sort.*`` vocabulary (one root ``sort`` span per call):

* ``sort.encode`` - keys to bit planes or order-preserving unsigned keys;
* ``sort.h2d`` - a host array handed to the device (counter ``h2d_bytes``);
* ``sort.dispatch`` - calls of jitted functions (enqueue, not run time);
  ``radix`` counts ``radix_dense``, 1 where its rows take the dense
  one-hot passes of ``radix_select.radix_sort_keys``;
* ``sort.readback`` - a device array read to the host, which waits for the
  device (counters ``readbacks``, ``d2h_bytes``);
* ``sort.rank_to_perm`` - the rank ring inverted on the host (``pallas-tns``
  past ``fused_tns.DEVICE_PERM_MAX`` emissions; below it the device finds
  the permutation's slots, counted as ``device_perm``);
* ``sort.finish`` - the result's slice, value gather and ``SortResult``.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16


class Record(NamedTuple):
    name: str
    start_ns: int             # time.perf_counter_ns()
    end_ns: int               # 0 while the span is open
    call: int                 # sequence number of the root span
    parent: int               # index of the parent record, -1 for a root
    counts: Optional[dict]    # a root's counters; None on other spans


class Recorder:
    """The buffer behind ``span`` and ``count``.  Spans nest per thread."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._local = threading.local()
        self.clear()

    def clear(self) -> None:
        """Forget every record, the dropped count and the call numbers;
        call it between calls, with no span open."""
        with self._lock:
            self._rows: list[list] = []
            self._calls = 0
            self.dropped = 0

    def records(self) -> list[Record]:
        with self._lock:
            return [Record(*r[:5], None if r[5] is None else dict(r[5]))
                    for r in self._rows]

    def span(self, name: str) -> "_Span":
        return _Span(self, name)

    def count(self, name: str, n: int) -> None:
        stack = getattr(self._local, "stack", None)
        if stack:
            counts = stack[0][1][5]
            counts[name] = counts.get(name, 0) + n

    def _open(self, name: str) -> Optional[list]:
        stack = self._local.__dict__.setdefault("stack", [])
        with self._lock:
            if len(self._rows) >= self.capacity:
                self.dropped += 1
                return None
            if stack:
                parent, row = stack[-1]
                call, counts = row[3], None
            else:
                parent, call, counts = -1, self._calls, {}
                self._calls += 1
            row = [name, time.perf_counter_ns(), 0, call, parent, counts]
            self._rows.append(row)
            stack.append((len(self._rows) - 1, row))
        return row

    def _close(self, row: list) -> None:
        row[2] = time.perf_counter_ns()
        self._local.stack.pop()


class _Span:
    __slots__ = ("_recorder", "_name", "_annotation", "_row")

    def __init__(self, recorder: Recorder, name: str):
        self._recorder, self._name = recorder, name

    def __enter__(self):
        # an annotation made while no profiler records is a no-op, so it
        # is made only when one does
        self._annotation = self._row = None
        if TraceAnnotation.is_enabled():
            self._annotation = TraceAnnotation(self._name)
            self._annotation.__enter__()
            self._row = self._recorder._open(self._name)
        return self

    def __exit__(self, *exc):
        if self._row is not None:
            self._recorder._close(self._row)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


_RECORDER = Recorder()
span = _RECORDER.span
count = _RECORDER.count
records = _RECORDER.records
clear = _RECORDER.clear


def dropped() -> int:
    """Spans not kept because the buffer was full."""
    return _RECORDER.dropped


def to_device(a: np.ndarray) -> jax.Array:
    """``jnp.asarray`` of a host array, in a ``sort.h2d`` span."""
    with span("sort.h2d"):
        count("h2d_bytes", a.nbytes)
        return jnp.asarray(a)


def to_host(a) -> np.ndarray:
    """``np.asarray`` of a device array, in a ``sort.readback`` span; a
    host array (or None) passes through uncounted."""
    if not isinstance(a, jax.Array):
        return a if a is None else np.asarray(a)
    with span("sort.readback"):
        out = np.asarray(a)
        count("readbacks", 1)
        count("d2h_bytes", out.nbytes)
    return out
