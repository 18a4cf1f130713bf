"""Autotuner for the fused Pallas TNS kernel: sweep (block_rows, unroll)
per (fmt, N, m, B, pallas-mode) cell and persist the winning table.

ADS-IMC's point — the best engine/kernel configuration depends on data
quantity and type — applied to our own kernel: the grid block height
(instances per program) and the episode unroll factor trade VMEM
residency against trip overhead differently per workload shape.  The
winning table ships inside ``BENCH_pallas_tns.json`` (written by
``benchmarks/bench_kernels.py``), the ``pallas-tns`` engine consults it
when the caller does not pin the knobs, and CI replays it as a perf
regression gate (``benchmarks.run --smoke-pallas``).

Keys embed the pallas mode (compiled / interpret / jnp) so a table tuned
on a TPU host never steers a CPU interpret run and vice versa.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.kernels import backend

#: block_rows == 0 encodes "whole batch in one grid program" (JSON-stable
#: stand-in for None)
DEFAULT_PARAMS = {"block_rows": 0, "unroll": 1}

BENCH_ARTIFACT = "BENCH_pallas_tns.json"


def cell_key(fmt: str, n: int, m: int, b: int,
             mode: Optional[str] = None) -> str:
    """Stable table key for one workload cell (``m`` = emitted numbers:
    N for a full sort, ``stop_after`` for top-m)."""
    return f"{fmt}|N{n}|m{m}|B{b}|{mode or backend.mode()}"


def candidate_params(b: int) -> List[Dict[str, int]]:
    """The sweep lattice: block heights that divide into the batch
    usefully, crossed with episode unroll factors.  A block shorter than
    the batch spans whole 8-row sublane tiles, so 8 is the smallest."""
    rows = [r for r in (0, 16, 8) if r == 0 or r < b]
    return [{"block_rows": r, "unroll": u} for r in rows for u in (1, 2, 4)]


def _gen_batch(fmt: str, width: int, n: int, b: int, seed: int):
    rng = np.random.default_rng(seed)
    if fmt == "unsigned":
        return rng.integers(0, 1 << width, (b, n))
    if fmt == "twos":
        half = 1 << (width - 1)
        return rng.integers(-half, half, (b, n))
    if fmt == "signmag":
        half = 1 << (width - 2)
        return rng.integers(-half, half, (b, n))
    return rng.standard_normal((b, n)).astype(np.float16)


def measure_cell(*, fmt: str, width: int, n: int, m: int, b: int,
                 k: int = 2, reps: int = 3, seed: int = 0,
                 cands: Optional[Sequence[Dict[str, int]]] = None
                 ) -> Dict[str, object]:
    """Time every candidate on one cell; returns the winner plus the full
    sweep (medians in us per call, compile excluded)."""
    from repro.kernels import fused_tns
    x = _gen_batch(fmt, width, n, b, seed)
    stop = None if m >= n else m
    rows = []
    for cand in (cands or candidate_params(b)):
        br = cand["block_rows"] or None
        kw = dict(width=width, k=k, fmt=fmt, stop_after=stop,
                  block_rows=br, unroll=cand["unroll"])
        np.asarray(fused_tns.fused_tns_sort(x, **kw).perm)   # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(fused_tns.fused_tns_sort(x, **kw).perm)
            ts.append(time.perf_counter() - t0)
        rows.append({**cand, "us": round(float(np.median(ts)) * 1e6, 1)})
    best = min(rows, key=lambda r: r["us"])
    return {"block_rows": best["block_rows"], "unroll": best["unroll"],
            "us": best["us"], "sweep": rows}


def sweep(cells: Sequence[Dict[str, int]], *, reps: int = 3,
          seed: int = 0) -> Dict[str, Dict[str, object]]:
    """Tune every cell: ``cells`` entries carry fmt/width/n/m/b (+k)."""
    table: Dict[str, Dict[str, object]] = {}
    for cell in cells:
        key = cell_key(cell["fmt"], cell["n"], cell["m"], cell["b"])
        table[key] = measure_cell(
            fmt=cell["fmt"], width=cell["width"], n=cell["n"],
            m=cell["m"], b=cell["b"], k=cell.get("k", 2), reps=reps,
            seed=seed)
    return table


def save_table(table: Dict[str, Dict[str, object]], path) -> None:
    Path(path).write_text(
        json.dumps({"autotune": table}, indent=2, sort_keys=True) + "\n")


def load_table(path) -> Dict[str, Dict[str, object]]:
    """Load an autotune table from a sweep file or a full BENCH artifact
    (both nest it under the "autotune" key)."""
    doc = json.loads(Path(path).read_text())
    return doc.get("autotune", doc)


_DEFAULT_CACHE: Dict[str, object] = {}


def default_table() -> Dict[str, Dict[str, object]]:
    """The committed table (repo-root BENCH artifact), cached on mtime so
    interactive regeneration is picked up without a process restart."""
    path = Path(__file__).resolve().parents[3] / BENCH_ARTIFACT
    if not path.exists():
        return {}
    mtime = path.stat().st_mtime_ns
    if _DEFAULT_CACHE.get("mtime") != mtime:
        try:
            _DEFAULT_CACHE["table"] = load_table(path)
        except (ValueError, OSError):
            _DEFAULT_CACHE["table"] = {}
        _DEFAULT_CACHE["mtime"] = mtime
    return _DEFAULT_CACHE["table"]          # type: ignore[return-value]


def best_params(fmt: str, n: int, m: int, b: int, *,
                mode: Optional[str] = None,
                table: Optional[Dict[str, Dict[str, object]]] = None
                ) -> Dict[str, int]:
    """Winning (block_rows, unroll) for a cell: exact table hit, else the
    nearest tuned cell of the same fmt+mode (log-space distance over
    (N, m, B) — shape, not magnitude, drives the optimum), else the
    defaults."""
    table = default_table() if table is None else table
    mode = mode or backend.mode()
    key = cell_key(fmt, n, m, b, mode)
    hit = table.get(key)
    if hit is not None:
        return {"block_rows": int(hit["block_rows"]),
                "unroll": int(hit["unroll"])}
    suffix = f"|{mode}"
    best, best_d = None, None
    for k in sorted(table):
        if not (k.startswith(f"{fmt}|") and k.endswith(suffix)):
            continue
        try:
            kn, km, kb = (int(part[1:]) for part in k.split("|")[1:4])
        except ValueError:
            continue
        d = sum(abs(np.log2(max(a, 1)) - np.log2(max(x, 1)))
                for a, x in ((kn, n), (km, m), (kb, b)))
        if best_d is None or d < best_d:
            best, best_d = table[k], d
    if best is not None:
        return {"block_rows": int(best["block_rows"]),
                "unroll": int(best["unroll"])}
    return dict(DEFAULT_PARAMS)
