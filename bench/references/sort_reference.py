"""Plain reference for the sort deployments, and the comparison that
decides ``correct``.

It imports nothing of the program under test.  What it holds:

* ``permutation``: the stable order of unsigned keys (ties: lowest index
  first), by ``numpy.argsort(kind="stable")``;
* ``tns_counters``: the paper's TNS controller (arXiv:2309.10350 §2.2,
  Supplementary S4/S7/S12), written out plainly for unsigned ascending
  binary planes with a k-deep drop-oldest LIFO.  It counts controller
  cycles, digit reads and redundant reload cycles, the observables that a
  TNS engine returns beside the permutation;
* ``compare``: every answer of the window against those;
* ``control_call``: the reference put in the program's place with one
  guarantee broken, as the control that must come out not correct.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

# Each compared number is a count of wrong rows: the comparison is exact.
LIMITS = {"rows_wrong": 0, "counter_rows_wrong": 0}
# Pool entries, drawn from the seed, whose controller counters are
# recomputed by the plain controller (it runs in Python, about a tenth of
# a second for a 512 x 1024 request of 8-bit keys at m = 1).
COUNTER_SAMPLE = 10


def permutation(keys: np.ndarray, ascending: bool,
                stop_after: int | None) -> np.ndarray:
    """Indices of the first ``stop_after`` extrema along the last axis, in
    emission order; ties leave lowest index first."""
    # unsigned keys sort in their own type (numpy radix-sorts 8 and 16
    # bits); ~key = max - key reverses their order for a descending sort
    perm = np.argsort(keys if ascending else ~keys, axis=-1, kind="stable")
    return perm if stop_after is None else perm[..., :stop_after]


def tns_counters(keys: np.ndarray, width: int, k: int,
                 stop_after: int | None) -> tuple[int, int, int]:
    """(cycles, digit reads, redundant reload cycles) of the TNS controller
    emitting the ``stop_after`` smallest of one array of unsigned keys."""
    n = keys.shape[0]
    shifts = np.arange(width - 1, -1, -1, dtype=np.uint64)
    digits = ((keys.astype(np.uint64)[None, :] >> shifts[:, None])
              & np.uint64(1)).astype(np.int8)          # (W, N), MSB first
    stop_n = n if stop_after is None else min(stop_after, n)
    alive = np.ones(n, bool)
    valid = alive.copy()
    lifo: list[tuple[int, np.ndarray]] = []
    col, reload_pending = 0, False
    cycles = drs = reloads = emitted = 0

    def emit(idx: int) -> None:
        nonlocal emitted
        alive[idx] = False
        valid[idx] = False
        emitted += 1

    while emitted < stop_n and alive.any():
        cycles += 1
        if reload_pending:
            # pop drained nodes one per cycle; the first live node (or the
            # root, with an empty LIFO) is the new working set
            reload_pending = False
            spent = False
            while True:
                if not lifo:
                    valid, col = alive.copy(), 0
                    break
                node_col, status = lifo[-1]
                live = status & alive
                if live.any():
                    valid, col = live, node_col
                    break
                lifo.pop()
                if lifo and not (lifo[-1][1] & alive).any():
                    reload_pending, spent = True, True
                    reloads += 1
                    break
            if spent:
                continue
        if valid.sum() == 1:                       # last-number check
            emit(int(np.flatnonzero(valid)[0]))
            reload_pending = bool(alive.any())
            continue
        if col >= width:                           # duplicates past the LSB
            emit(int(np.flatnonzero(valid)[0]))
            if not valid.any():
                reload_pending = bool(alive.any())
            continue
        row = digits[col]
        vals = row[valid]
        drs += 1
        if (vals != vals[0]).any():                # mixed read
            if k > 0:
                if len(lifo) == k:
                    lifo.pop(0)
                lifo.append((col + 1, valid.copy()))
            valid = valid & (row == 0)
        if valid.sum() == 1:
            emit(int(np.flatnonzero(valid)[0]))
            reload_pending = bool(alive.any())
            continue
        if col == width - 1:
            emit(int(np.flatnonzero(valid)[0]))
            col = width
            if not valid.any():
                reload_pending = bool(alive.any())
            continue
        col += 1
    return cycles, drs, reloads


def compare(cfg: dict, pool, answers, rng: np.random.Generator):
    """Compare every answer of a window with the reference.

    ``pool``: the requests (``x``, ``stop_after``); ``answers``: one per
    call that returned, ``(pool index, result)``, where a result has
    ``indices``, ``values`` and, for a TNS engine, ``cycles``, ``drs`` and
    ``reload_cycles``.  Returns the compared numbers, each as
    ``{"value", "limit"}``, and how many answers were wrong."""
    asc = cfg["ascending"]
    by_request: dict[int, list[int]] = {}
    for a, (i, _) in enumerate(answers):
        by_request.setdefault(i, []).append(a)
    bad = np.zeros(len(answers), bool)
    rows_wrong = 0
    for i, calls in by_request.items():
        want = permutation(pool[i].x, asc, pool[i].stop_after)
        for a in calls:
            wrong = _rows_wrong(pool[i].x, want, answers[a][1])
            rows_wrong += wrong
            bad[a] = wrong > 0
    out = {"rows_wrong": rows_wrong}
    if cfg.get("counters") == "tns":
        out["counter_rows_wrong"] = _compare_counters(cfg, pool, answers,
                                                      rng, bad)
    checks = {name: {"value": v, "limit": LIMITS[name]}
              for name, v in out.items()}
    return checks, int(bad.sum())


def _rows_wrong(x, ref, res) -> int:
    idx, vals = np.asarray(res.indices), np.asarray(res.values)
    if idx.shape != ref.shape or vals.shape != ref.shape:
        return ref.shape[0]
    ok = ((idx == ref).all(-1)
          & (vals == np.take_along_axis(x, ref, -1)).all(-1))
    return int((~ok).sum())


def _compare_counters(cfg, pool, answers, rng, bad) -> int:
    """Rows whose counters differ from the plain controller's, over every
    answer to a sample of the pool drawn from the seed."""
    if not cfg["ascending"]:
        raise ValueError("the plain controller models ascending sorts")
    used = sorted({i for i, _ in answers})
    sample = set(rng.choice(used, min(COUNTER_SAMPLE, len(used)),
                            replace=False).tolist())
    want = {i: np.array([tns_counters(row, cfg["width"], cfg["k"],
                                      pool[i].stop_after)
                         for row in pool[i].x]) for i in sample}
    wrong = 0
    for a, (i, res) in enumerate(answers):
        if i not in sample:
            continue
        fields = [getattr(res, f, None)
                  for f in ("cycles", "drs", "reload_cycles")]
        got = (None if any(f is None for f in fields) else
               np.stack([np.asarray(f).reshape(-1) for f in fields], -1))
        rows = (want[i].shape[0] if got is None or got.shape != want[i].shape
                else int((got != want[i]).any(-1).sum()))
        wrong += rows
        bad[a] |= rows > 0
    return wrong


class ControlResult(NamedTuple):
    indices: np.ndarray
    values: np.ndarray


def control_call(cfg: dict):
    """The reference in the program's place, with its keys read one bit
    short of the configured width (the least significant digit plane left
    out): the step below the stated exactness that would tempt a faster
    path.  It returns no counters."""
    asc, shift = cfg["ascending"], np.uint64(1)

    def call(x: np.ndarray, stop_after: int | None) -> ControlResult:
        perm = permutation(x.astype(np.uint64) >> shift, asc, stop_after)
        return ControlResult(perm, np.take_along_axis(x, perm, -1))
    return call
