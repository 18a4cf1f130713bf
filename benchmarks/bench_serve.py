"""Serving benchmark: continuous batching vs the one-shot loop at an
identical request mix, plus dispatch diversity and determinism checks.

The headline number is *aggregate device throughput* (elements emitted
per simulated microsecond): the continuous batcher packs compatible
requests into the batched TNS machine, so a step costs the MAX of its
members' incremental cycles where the one-shot loop pays the SUM.

    PYTHONPATH=src python -m benchmarks.bench_serve --out BENCH_serve.json
    PYTHONPATH=src python -m benchmarks.bench_serve --smoke
"""
from __future__ import annotations

import argparse
import json

from repro import serving
from repro.kernels import backend
from repro.runtime import faults

# (n_requests, n, chunk, mean_gap_us): the gap is far below the mean
# service time so both arms run saturated — the regime where batching
# pays; an idle trace is bounded by arrivals on both arms.
FULL = dict(n_requests=40, n=48, chunk=8, mean_gap_us=0.05)
SMOKE = dict(n_requests=12, n=32, chunk=16, mean_gap_us=0.05)


def _arm(kind: str, cfg: dict, seed: int = 0) -> dict:
    trace = serving.make_trace(cfg["n_requests"], seed=seed, n=cfg["n"],
                               mean_gap_us=cfg["mean_gap_us"])
    if kind == "continuous":
        orch = serving.Orchestrator(
            clock=serving.SimulatedClock(),
            cfg=serving.OrchestratorConfig(chunk=cfg["chunk"]))
        return orch.run(trace)
    return serving.oneshot_loop(trace)


def faulted_point(cfg: dict) -> dict:
    """A short faulted trace: the dispatcher must route everything to
    verified engines (resilient:*/mb-ft) to satisfy the quality floor."""
    trace = serving.make_trace(6, seed=1, n=cfg["n"],
                               mean_gap_us=cfg["mean_gap_us"],
                               classes=("bulk-latency", "float-latency"),
                               quality_floor=0.99)
    orch = serving.Orchestrator(
        clock=serving.SimulatedClock(),
        cfg=serving.OrchestratorConfig(chunk=cfg["chunk"]))
    with faults.inject(faults.FaultSpec(ber=0.01, seed=0)):
        rep = orch.run(trace)
    return {"engines": rep["engines"], "completed": rep["completed"],
            "accepted": rep["accepted"]}


def build_report(smoke: bool = False) -> dict:
    cfg = SMOKE if smoke else FULL
    cont = _arm("continuous", cfg)
    ones = _arm("oneshot", cfg)
    # determinism: an identical second run must match on every field that
    # lives in simulated device time (wall_ms is informational only)
    cont2 = _arm("continuous", cfg)
    a, b = dict(cont), dict(cont2)
    a.pop("wall_ms"), b.pop("wall_ms")
    trace = serving.make_trace(cfg["n_requests"], seed=0, n=cfg["n"],
                               mean_gap_us=cfg["mean_gap_us"])
    return {
        "bench": "serve",
        "env": backend.env_stamp(),
        "config": dict(cfg),
        "trace_mix": serving.trace_mix(trace),
        "continuous": cont,
        "oneshot": ones,
        "speedup": round(cont["throughput_elems_per_us"]
                         / max(1e-12, ones["throughput_elems_per_us"]), 3),
        "deterministic": a == b,
        "faulted": faulted_point(cfg),
    }


def check(rep: dict) -> list:
    """The acceptance assertions (shared by --smoke and the CI lane)."""
    cont, ones = rep["continuous"], rep["oneshot"]
    failures = []
    if cont["throughput_elems_per_us"] <= ones["throughput_elems_per_us"]:
        failures.append(
            f"continuous batching must beat one-shot: "
            f"{cont['throughput_elems_per_us']:.1f} <= "
            f"{ones['throughput_elems_per_us']:.1f} elems/us")
    if len(cont["engines"]) < 3:
        failures.append(f"budget dispatch picked only "
                        f"{sorted(cont['engines'])} (< 3 engines)")
    if cont["completed"] != cont["accepted"] or cont["failed"] > 0:
        failures.append(f"continuous arm dropped work: {cont}")
    if not rep["deterministic"]:
        failures.append("simulated-clock run is not deterministic")
    f = rep["faulted"]
    if f["completed"] != f["accepted"]:
        failures.append(f"faulted arm dropped work: {f}")
    bad = [e for e in f["engines"]
           if not (e.startswith("resilient:") or e == "mb-ft")]
    if bad:
        failures.append(f"faulted trace used unverified engines: {bad}")
    return failures


def run(report) -> None:
    """benchmarks.run section hook."""
    rep = build_report(smoke=True)
    for arm in ("continuous", "oneshot"):
        d = dict(rep[arm])
        report(f"serve_{arm}", d.pop("wall_ms") * 1e3, {
            "throughput_elems_per_us": d["throughput_elems_per_us"],
            "p50_latency_us": d["p50_latency_us"],
            "p99_latency_us": d["p99_latency_us"],
            "engines": d["engines"],
        })
    report("serve_speedup", 0.0, {"speedup": rep["speedup"],
                                  "deterministic": rep["deterministic"]})
    report("serve_faulted", 0.0, rep["faulted"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write JSON report here")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced trace + hard assertions (CI lane)")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()
    rep = build_report(smoke=args.smoke)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2, sort_keys=True)
        print(f"wrote {args.out}")
    else:
        print(json.dumps(rep, indent=2, sort_keys=True))
    if args.smoke:
        failures = check(rep)
        if failures:
            print(f"# SERVE SMOKE FAILED: {failures}")
            return 1
        print("# SERVE SMOKE OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
