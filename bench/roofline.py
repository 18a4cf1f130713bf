"""The problem's bytes, and the chip's published peaks.

A sort or a selection does no floating-point work, and the table of peaks
publishes no integer vector rate for this chip, so the only published peak
that bounds it is HBM bandwidth.  A roofline share here is bytes-bound:
the least time the chip could take to read the keys and write the
answer, over the time the kernel took.

The bytes are the problem's, not the implementation's, so that a share
reads the same work whatever implements it:

* top-m: the keys in, at their width, and m int32 indices out per array;
* full sort: the keys in, and one int32 index out per key.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
INDEX_BYTES = 4


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}; have {sorted(table)}")
    return table[device_kind]


def problem_bytes(batch: int, n: int, key_bytes: int,
                  stop_after: int | None) -> int:
    """Bytes a call must move at least: its keys in, its indices out."""
    out = n if stop_after is None else min(stop_after, n)
    return batch * n * key_bytes + batch * out * INDEX_BYTES


def answered_bytes(run) -> int:
    """Problem bytes of every call of a run's window that returned."""
    return sum(problem_bytes(*req.x.shape, req.x.dtype.itemsize,
                             req.stop_after)
               for req in (run.pool[c.pool_index] for c in run.done))


def share_pct(nbytes: float, peak_bytes_per_s: float,
              device_s: float) -> float:
    """Least time for ``nbytes`` at the peak, over the time taken, in %."""
    return 100.0 * (nbytes / peak_bytes_per_s) / device_s
