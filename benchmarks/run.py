"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only sort,apps,...]
    PYTHONPATH=src python -m benchmarks.run --smoke      # CI fast pass
"""
from __future__ import annotations

import argparse
import json
import sys


def _report(name: str, us: float, derived: dict | None = None) -> None:
    payload = json.dumps(derived or {}, sort_keys=True)
    print(f"{name},{us:.1f},{payload}", flush=True)


def smoke() -> int:
    """Fast CI pass over the engine registry: every engine sorts a small
    dataset, every permutation matches, the in-model dispatchers agree
    with lax.  Returns a process exit code."""
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import sort as sort_engine

    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**16, 64).astype(np.uint16)
    ref = None
    failures = []
    for name, spec in sorted(sort_engine.engines().items()):
        try:
            res = sort_engine.sort(x, engine=name, k=2)
        except sort_engine.EngineUnsupported:
            continue
        perm = np.asarray(res.indices)
        if ref is None:
            ref = perm
        ok = bool(np.array_equal(perm, ref))
        _report(f"smoke_engine_{name}", 0.0,
                {"ok": ok, "mode": spec.mode,
                 "cycles": None if res.cycles is None
                 else int(np.mean(res.cycles))})
        if not ok:
            failures.append(name)
    # top-m engines that refuse full sorts still must agree on the prefix
    res = sort_engine.sort(x, engine="pallas-topk", stop_after=8)
    ok = bool(np.array_equal(np.asarray(res.indices), ref[:8]))
    _report("smoke_engine_pallas-topk_top8", 0.0, {"ok": ok})
    if not ok:
        failures.append("pallas-topk")
    # batched dispatch parity (B, N)
    xb = rng.standard_normal((8, 32)).astype(np.float32)
    a = sort_engine.sort(xb, engine="tns", k=2).indices
    b = sort_engine.sort(xb, engine="radix").indices
    ok = bool(np.array_equal(a, b))
    _report("smoke_batched_parity", 0.0, {"ok": ok})
    if not ok:
        failures.append("batched")
    # in-model dispatchers
    lg = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    vl, _ = jax.lax.top_k(lg, 4)
    for name in sort_engine.TOPK_ENGINES:
        v, _ = sort_engine.topk(lg, 4, engine=name)
        ok = bool(jnp.allclose(v, vl))
        _report(f"smoke_topk_{name}", 0.0, {"ok": ok})
        if not ok:
            failures.append(f"topk-{name}")
    if failures:
        print(f"# SMOKE FAILED: {failures}", flush=True)
        return 1
    print("# SMOKE OK", flush=True)
    return 0


def smoke_faults() -> int:
    """Fault-injection CI lane: zero-fault parity of every resilient
    wrapper, exact repair under the dead-bank + 1% BER spec, quality at
    the paper's operating BER, graceful degradation at 20% BER."""
    import numpy as np
    from repro import sort as sort_engine
    from repro.core import device_model as dm
    from repro.runtime import faults

    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**16, 64).astype(np.uint16)
    failures = []
    # zero-fault parity: resilient:<e> returns <e>'s permutation untouched
    for name, spec in sorted(sort_engine.engines().items()):
        if name.startswith("resilient:"):
            continue
        try:
            inner = sort_engine.sort(x, engine=name, k=2)
            res = sort_engine.sort(x, engine=f"resilient:{name}", k=2)
        except sort_engine.EngineUnsupported:
            continue
        ok = (bool(np.array_equal(res.indices, inner.indices))
              and res.quality == 1.0 and not res.degraded
              and res.repairs == 0 and res.retries == 0)
        _report(f"faults_parity_{name}", 0.0, {"ok": ok})
        if not ok:
            failures.append(f"parity:{name}")
    # dead bank + 1% BER: repaired to an exact sort, repairs visible
    spec = faults.FaultSpec(ber=0.01, dead_banks=(1,), banks=4, seed=3)
    for eng in ("resilient:tns", "mb-ft"):
        kw = {"banks": 4} if eng == "mb-ft" else {}
        with faults.inject(spec):
            res = sort_engine.sort(x, engine=eng, **kw)
        ok = (res.quality == 1.0 and not res.degraded and res.repairs > 0
              and bool(np.array_equal(res.values, np.sort(x))))
        _report(f"faults_deadbank_{eng}", 0.0,
                {"ok": ok, "repairs": res.repairs, "retries": res.retries,
                 "extra_cycles": res.extra_cycles})
        if not ok:
            failures.append(f"deadbank:{eng}")
    # paper's calibrated ML operating point: quality >= 0.99
    ber = dm.operating_ber(3)
    with faults.inject(faults.FaultSpec(ber=ber, seed=4)):
        res = sort_engine.sort(x, engine="resilient:tns")
    ok = res.quality >= 0.99 and not res.degraded
    _report("faults_operating_ber", 0.0,
            {"ok": ok, "ber": round(ber, 6), "quality": res.quality})
    if not ok:
        failures.append("operating-ber")
    # 20% BER (Fig. S28's tolerance edge): degrade, don't raise
    with faults.inject(faults.FaultSpec(ber=0.20, seed=5)):
        res = sort_engine.sort(x, engine="resilient:tns")
    ok = res.degraded and res.quality is not None and res.retries > 0
    _report("faults_degrade_20pct", 0.0,
            {"ok": ok, "quality": res.quality, "retries": res.retries})
    if not ok:
        failures.append("degrade-20pct")
    if failures:
        print(f"# FAULT SMOKE FAILED: {failures}", flush=True)
        return 1
    print("# FAULT SMOKE OK", flush=True)
    return 0


def smoke_serve() -> int:
    """Serving CI lane: continuous batching beats the one-shot loop at an
    identical request mix, the budget dispatcher spreads across >= 3
    engines, a faulted trace routes to verified engines only, and the
    whole loop is deterministic on the simulated clock."""
    from benchmarks import bench_serve

    rep = bench_serve.build_report(smoke=True)
    for arm in ("continuous", "oneshot"):
        d = rep[arm]
        _report(f"serve_{arm}", d["wall_ms"] * 1e3,
                {"throughput_elems_per_us": d["throughput_elems_per_us"],
                 "engines": d["engines"]})
    _report("serve_speedup", 0.0, {"speedup": rep["speedup"],
                                   "deterministic": rep["deterministic"]})
    failures = bench_serve.check(rep)
    if failures:
        print(f"# SERVE SMOKE FAILED: {failures}", flush=True)
        return 1
    print("# SERVE SMOKE OK", flush=True)
    return 0


def smoke_pallas() -> int:
    """Fused-kernel CI lane: interpret-mode permutation + cycle parity of
    the fused Pallas TNS kernel against the while_loop machine, the
    autotune round-trip, and a ratio-based perf gate — measured
    fused/machine speedup must stay within 0.9x of the committed
    ``BENCH_pallas_tns.json`` baseline (skipped when the committed
    artifact was produced under a different backend/pallas mode)."""
    from benchmarks import bench_pallas_tns

    rep = bench_pallas_tns.build_report(smoke=True)
    for r in rep["head_to_head"]:
        _report(f"pallas_{r['fmt']}_n{r['n']}_m{r['m']}_b{r['b']}",
                r["fused_us"],
                {"machine_us": r["machine_us"],
                 "speedup_vs_machine": r["speedup_vs_machine"],
                 "parity_ok": r["parity_ok"],
                 "cycles_match": r["cycles_match"]})
    acc = rep["acceptance"]
    _report("pallas_acceptance", 0.0, acc)
    failures = bench_pallas_tns.check(
        rep, bench_pallas_tns.committed_artifact())
    if failures:
        print(f"# PALLAS SMOKE FAILED: {failures}", flush=True)
        return 1
    print("# PALLAS SMOKE OK", flush=True)
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section filter "
                         "(sort,apps,sweeps,kernels,pallas,resilience,"
                         "serve)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast engine-registry pass for CI")
    ap.add_argument("--smoke-faults", action="store_true",
                    help="fault-injection + repair pass for CI")
    ap.add_argument("--smoke-serve", action="store_true",
                    help="continuous-batching serving pass for CI")
    ap.add_argument("--smoke-pallas", action="store_true",
                    help="fused Pallas TNS parity + perf-gate pass for CI")
    args, _ = ap.parse_known_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    print("name,us_per_call,derived")
    if args.smoke:
        sys.exit(smoke())
    if args.smoke_faults:
        sys.exit(smoke_faults())
    if args.smoke_serve:
        sys.exit(smoke_serve())
    if args.smoke_pallas:
        sys.exit(smoke_pallas())

    from benchmarks import (bench_apps, bench_kernels, bench_pallas_tns,
                            bench_resilience, bench_serve, bench_sort,
                            bench_sweeps)
    sections = {
        "sort": bench_sort.run,          # Fig 4f-g, S18/S19, Table S5
        "apps": bench_apps.run,          # Fig 5, Fig 6, Fig S28
        "sweeps": bench_sweeps.run,      # S11, S12, Fig 2e-g
        "kernels": bench_kernels.run,    # kernel micro-benchmarks
        "pallas": bench_pallas_tns.run,  # fused TNS vs machine vs XLA
        "resilience": bench_resilience.run,  # Fig. S28 + §2.3.1 faults
        "serve": bench_serve.run,        # continuous batching vs one-shot
    }
    chosen = (args.only.split(",") if args.only else list(sections))
    for name in chosen:
        print(f"# --- {name} ---")
        sections[name](_report)


if __name__ == "__main__":
    main()
