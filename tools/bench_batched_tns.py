"""Measure the batched-TNS acceptance benchmark and write BENCH_batched_tns.json.

Compares, at B=64 / N=256 / W=16 / k=2 (the serving-shaped workload):

  * ``loop``    — a Python loop over single-instance public-API calls
                  (encode + one compiled dispatch + host materialization
                  per request; the pre-refactor serving pattern), vs
  * ``batched`` — one ``tns_sort_batch`` call: one batch encode, ONE
                  compiled dispatch stepping all 64 controllers in
                  lockstep on the bit-parallel machine, one readback.

Both sides produce identical permutations and per-instance cycle counts
(asserted here and in tests/test_sort_engine.py).

    PYTHONPATH=src python tools/bench_batched_tns.py [--out BENCH_batched_tns.json]
"""
from __future__ import annotations

import argparse
import json
import platform
import statistics
import time

import numpy as np

from repro.core import tns as jt
from repro.kernels import backend


def measure(B=64, N=256, W=16, k=2, reps=9, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 2**W, (B, N))

    def batched():
        return np.asarray(jt.tns_sort_batch(data, width=W, k=k).perm)

    def loop():
        return np.stack([
            np.asarray(jt.tns_sort(data[b], width=W, k=k).perm)
            for b in range(B)])

    pb, pl = batched(), loop()                 # compile + correctness
    assert np.array_equal(pb, pl), "batched/loop permutation mismatch"

    def bench(f):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return ts

    tb, tl = bench(batched), bench(loop)
    mb, ml = statistics.median(tb), statistics.median(tl)
    return {
        "config": {"B": B, "N": N, "W": W, "k": k, "reps": reps,
                   "seed": seed},
        "batched_ms": {"median": round(mb * 1e3, 2),
                       "min": round(min(tb) * 1e3, 2)},
        "loop_ms": {"median": round(ml * 1e3, 2),
                    "min": round(min(tl) * 1e3, 2)},
        "speedup_median": round(ml / mb, 2),
        "speedup_conservative": round(min(tl) / max(tb), 2),
        "permutations_identical": True,
        "host": {"machine": platform.machine(),
                 "python": platform.python_version()},
        "env": backend.env_stamp(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_batched_tns.json")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    result = measure()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(result, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
