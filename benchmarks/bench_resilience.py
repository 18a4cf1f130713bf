"""Resilience benchmark: sorting accuracy + repair cycle overhead vs BER
(Fig. S28's graceful-degradation shape), raw engine vs the
verify-and-repair wrapper, plus the dead-bank recovery point.

    PYTHONPATH=src python -m benchmarks.bench_resilience --out BENCH_resilience.json
    PYTHONPATH=src python -m benchmarks.bench_resilience --smoke
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.datasets import make_dataset
from repro import sort as sort_engine
from repro.core import device_model as dm
from repro.kernels import backend
from repro.runtime import faults

BERS = (0.0, 0.001, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2)


def _accuracy(x, res) -> float:
    """Fraction of emission positions holding the correct value."""
    expect = np.sort(np.asarray(x))
    got = np.asarray(res.values)
    return float(np.mean(expect == got))


def sweep(n: int = 64, width: int = 8, bers=BERS, seeds=(0, 1, 2),
          engine: str = "tns") -> dict:
    """Accuracy and cycle overhead vs BER for ``engine`` raw and wrapped."""
    points = []
    for ber in bers:
        raw_acc, res_acc, res_q = [], [], []
        overhead, repaired, degraded = [], 0, 0
        for seed in seeds:
            x = make_dataset("random", n, width, seed=seed)
            spec = faults.FaultSpec(ber=ber, seed=seed)
            with faults.inject(spec):
                raw = sort_engine.sort(x, engine=engine)
            raw_acc.append(_accuracy(x, raw))
            with faults.inject(spec):
                res = sort_engine.sort(x, engine=f"resilient:{engine}")
            res_acc.append(_accuracy(x, res))
            res_q.append(float(res.quality))
            base = int(np.sum(np.asarray(raw.cycles)))
            overhead.append(res.extra_cycles / max(1, base))
            repaired += int(res.repairs > 0 or res.retries > 0)
            degraded += int(res.degraded)
        points.append({
            "ber": ber,
            "raw_accuracy": round(float(np.mean(raw_acc)), 4),
            "resilient_accuracy": round(float(np.mean(res_acc)), 4),
            "quality": round(float(np.mean(res_q)), 4),
            "cycle_overhead": round(float(np.mean(overhead)), 3),
            "repaired_runs": repaired,
            "degraded_runs": degraded,
        })
    return {"engine": engine, "n": n, "width": width,
            "seeds": len(seeds), "points": points}


def dead_bank_point(n: int = 64, width: int = 8, banks: int = 4) -> dict:
    """The §2.3.1 fault story: one dead bank + calibrated read noise,
    repaired to an exact sort by remap + voting."""
    x = make_dataset("random", n, width, seed=3)
    spec = faults.FaultSpec(ber=0.01, dead_banks=(1,), banks=banks, seed=3)
    out = {}
    for eng in ("resilient:tns", "mb-ft"):
        kw = {"banks": banks} if eng == "mb-ft" else {}
        t0 = time.perf_counter()
        with faults.inject(spec):
            res = sort_engine.sort(x, engine=eng, **kw)
        wall = (time.perf_counter() - t0) * 1e3
        out[eng] = {
            "quality": float(res.quality),
            "exact": bool(np.array_equal(res.values, np.sort(x))),
            "repairs": res.repairs, "retries": res.retries,
            "degraded": res.degraded, "extra_cycles": res.extra_cycles,
            "wall_ms": round(wall, 1),
        }
    return out


def operating_point(n: int = 64, width: int = 8) -> dict:
    """Quality at the paper's calibrated multi-level operating BER."""
    ber = dm.operating_ber(3)
    x = make_dataset("random", n, width, seed=4)
    with faults.inject(faults.FaultSpec(ber=ber, seed=4)):
        res = sort_engine.sort(x, engine="resilient:tns")
    return {"ber": round(ber, 6), "quality": float(res.quality),
            "degraded": res.degraded}


def build_report(smoke: bool = False) -> dict:
    bers = (0.0, 0.01, 0.2) if smoke else BERS
    seeds = (0,) if smoke else (0, 1, 2)
    return {
        "bench": "resilience",
        "env": backend.env_stamp(),
        "sweep": sweep(bers=bers, seeds=seeds),
        "dead_bank": dead_bank_point(),
        "operating_point": operating_point(),
    }


def run(report) -> None:
    """benchmarks.run section hook."""
    rep = build_report(smoke=True)
    for p in rep["sweep"]["points"]:
        report(f"resilience_ber{p['ber']}", 0.0, p)
    for eng, d in rep["dead_bank"].items():
        report(f"resilience_deadbank_{eng}", d.pop("wall_ms"), d)
    report("resilience_operating_point", 0.0, rep["operating_point"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweep + hard assertions (CI lane)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    rep = build_report(smoke=args.smoke)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2)
        print(f"wrote {args.out}")
    else:
        print(json.dumps(rep, indent=2))
    if args.smoke:
        db = rep["dead_bank"]
        op = rep["operating_point"]
        hi = [p for p in rep["sweep"]["points"] if p["ber"] >= 0.2]
        failures = []
        if not (db["resilient:tns"]["exact"] and db["mb-ft"]["exact"]):
            failures.append("dead-bank repair not exact")
        if not (db["resilient:tns"]["repairs"] > 0
                and db["mb-ft"]["repairs"] > 0):
            failures.append("dead-bank repair reported no repairs")
        if op["quality"] < 0.99 or op["degraded"]:
            failures.append(f"operating-BER quality {op['quality']} < 0.99")
        if any(p["degraded_runs"] == 0 or p["quality"] <= 0 for p in hi):
            failures.append("20% BER should degrade gracefully "
                            "(degraded=True with a reported quality)")
        if failures:
            print(f"# RESILIENCE SMOKE FAILED: {failures}")
            return 1
        print("# RESILIENCE SMOKE OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
