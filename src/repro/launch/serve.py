"""Serving CLI — thin front-end over the production serving subsystem.

Default mode runs the continuous-batching orchestrator
(:mod:`repro.serving`) on a deterministic synthetic request trace: async
admission with backpressure, budget-aware engine dispatch over the sort
registry, and sustained-throughput metrics (p50/p99 latency, batch
occupancy, evictions) on a simulated device clock.

    PYTHONPATH=src python -m repro.launch.serve --requests 40 --n 48 \
        --mean-gap-us 0.1 --out serve.json

``--oneshot`` keeps the original model-decode driver: batched prefill +
decode with the paper's technique in the loop (comparison-free top-k
sampling via the sort-engine facade, engine-selectable MoE routing,
optional in-situ pruning masks).

    PYTHONPATH=src python -m repro.launch.serve --oneshot --arch olmo_1b \
        --batch 4 --prompt-len 16 --max-new 32 --top-k 32 --prune 0.3 \
        --router-impl radix

Both modes accept ``--fault-spec`` to serve from an imperfect array.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, Optional

import numpy as np

from repro import serving, sort as sort_engine
from repro.launch import compile_cache
from repro.runtime import faults
from repro.runtime.faults import run_step_with_retries


# ---------------------------------------------------------------------------
# Default mode: the continuous-batching serving loop.
# ---------------------------------------------------------------------------


def serve_requests(n_requests: int, *, n: int = 48, seed: int = 0,
                   mean_gap_us: float = 0.1, max_batch: int = 8,
                   chunk: int = 8, quality_floor: Optional[float] = None,
                   fault_spec: Optional[faults.FaultSpec] = None) -> Dict:
    """Run one synthetic trace through the orchestrator; returns the
    sustained-throughput summary (plus fault counters when injecting)."""
    trace = serving.make_trace(n_requests, seed=seed, n=n,
                               mean_gap_us=mean_gap_us,
                               quality_floor=quality_floor)
    orch = serving.Orchestrator(
        clock=serving.SimulatedClock(),
        cfg=serving.OrchestratorConfig(max_batch=max_batch, chunk=chunk))
    if fault_spec is not None:
        counters = faults.FaultCounters()
        with faults.inject(fault_spec, counters=counters):
            report = orch.run(trace)
        report["fault_counters"] = dataclasses.asdict(counters)
    else:
        report = orch.run(trace)
    report["trace_mix"] = serving.trace_mix(trace)
    return report


def _print_report(report: Dict) -> None:
    print(f"[serve] {report['completed']} completed / "
          f"{report['accepted']} accepted ({report['rejected']} rejected, "
          f"{report['expired']} expired, {report['failed']} failed) "
          f"in {report['ticks']} ticks / {report['sim_us']:.2f}us device")
    print(f"[serve] throughput {report['throughput_elems_per_us']:.1f} "
          f"elems/us  latency p50 {report['p50_latency_us']:.2f}us "
          f"p99 {report['p99_latency_us']:.2f}us")
    print(f"[serve] batch occupancy mean {report['mean_batch_occupancy']:.2f} "
          f"peak {report['peak_batch_occupancy']}  queue depth mean "
          f"{report['mean_queue_depth']:.2f}  evictions/tick "
          f"{report['evictions_per_tick']:.2f}")
    print(f"[serve] engine dispatches: {report['engines']}")
    if "fault_counters" in report:
        c = report["fault_counters"]
        print(f"[serve] fault counters: reads={c['reads']} "
              f"faults={c['faults_injected']} corrected={c['corrected']} "
              f"votes={c['votes']} delays={c['delays']}")


# ---------------------------------------------------------------------------
# --oneshot: the original prefill+decode model driver.
# ---------------------------------------------------------------------------


def serve(cfg, batch: int, prompt_len: int, max_new: int,
          mesh=None, top_k: int = 0, prune_rate: float = 0.0, seed: int = 0):
    import jax
    import jax.numpy as jnp

    from repro.data import pipeline as dp
    from repro.launch import mesh as mesh_lib
    from repro.launch import sharding as sh
    from repro.launch import steps as steps_lib
    from repro.models import sampling, shard, stacked
    from repro.pruning import insitu

    mesh = mesh or mesh_lib.make_host_mesh()
    dp_axes = mesh_lib.data_axes(mesh)
    wf = bool(cfg.frontend_tokens)
    max_len = prompt_len + max_new

    params = stacked.init_params(cfg, jax.random.PRNGKey(seed))
    pspecs = sh.param_specs(mesh, params)
    params = jax.device_put(params, sh.named(mesh, pspecs))

    if prune_rate > 0:
        # the paper's in-situ pruning (§3.2): TNS locates the p% smallest
        # magnitudes in each MLP input row-block at serve time (masking an
        # input lane == zeroing its weight row, Algorithm S2)
        params, pstats = insitu.prune_params(params, cfg, prune_rate)
        print(f"[serve] in-situ pruned: weight sparsity "
              f"{pstats['weight_sparsity']:.1%}")

    prefill = steps_lib.make_prefill_step(cfg, with_frontend=wf)
    decode = steps_lib.make_decode_step(cfg, with_frontend=wf)
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, prompt_len)),
                         jnp.int32)
    fe = dp.frontend_stub(cfg, batch) if wf else None

    with mesh:
        with shard.mesh_axes(dp_axes, "model", mesh):
            caches = stacked.init_cache(cfg, batch, max_len)
            t0 = time.monotonic()
            args = (params, prompt, caches) + ((fe,) if wf else ())
            logits, caches = jax.jit(prefill)(*args)
            jax.block_until_ready(logits)
            prefill_s = time.monotonic() - t0

            jd = jax.jit(decode)
            key = jax.random.PRNGKey(seed)
            tok = sampling.sample_logits(logits[:, -1, :], key, top_k)[:, None]
            out = [prompt, tok]
            pos = jnp.full((batch,), prompt_len - 1, jnp.int32)
            t0 = time.monotonic()
            for i in range(max_new - 1):
                key, sk = jax.random.split(key)
                pos = pos + 1
                args = (params, tok, pos, caches) + ((fe,) if wf else ())
                logits, caches = jd(*args)
                tok = sampling.sample_logits(logits[:, -1, :], sk,
                                             top_k)[:, None]
                out.append(tok)
            seq = jnp.concatenate(out, axis=1)
            jax.block_until_ready(seq)
            decode_s = time.monotonic() - t0
    return {
        "tokens": np.asarray(seq),
        "logits": np.asarray(logits[:, -1, :]),   # the last sampled step's
        "prefill_s": prefill_s,
        "decode_tok_per_s": batch * (max_new - 1) / max(decode_s, 1e-9),
        "pruned": prune_rate,
    }


def _oneshot_main(args) -> None:
    from repro import configs

    if not args.arch:
        raise SystemExit("--oneshot requires --arch")
    cfg = configs.get_config(args.arch)
    if args.router_impl:
        cfg = dataclasses.replace(cfg, router_impl=args.router_impl)
    if not args.full_size:
        cfg = cfg.reduced(n_layers=args.layers, d_model=args.d_model,
                          vocab=args.vocab)
        if cfg.ssm_state:
            cfg = dataclasses.replace(
                cfg, ssm_chunk=min(cfg.ssm_chunk, args.prompt_len))
    if args.fault_spec:
        spec = faults.parse_spec(args.fault_spec)
        counters = faults.FaultCounters()
        probe_state: Dict = {}

        def attempt():
            with faults.inject(spec, counters=counters):
                # pre-flight: a resilient sort on the faulted array; a
                # degraded result means even the repair ladder cannot
                # trust this array — retry (fresh read noise), then fail
                probe = sort_engine.sort(
                    np.arange(64, dtype=np.uint16)[::-1].copy(),
                    engine="resilient:tns")
                probe_state.update(
                    quality=float(probe.quality), repairs=probe.repairs,
                    retries=probe.retries, degraded=probe.degraded)
                print(f"[serve] fault pre-flight: quality="
                      f"{probe.quality:.3f} repairs={probe.repairs} "
                      f"retries={probe.retries} degraded={probe.degraded}")
                if probe.degraded:
                    raise faults.VerificationError(
                        "fault pre-flight degraded")
                return serve(cfg, args.batch, args.prompt_len, args.max_new,
                             top_k=args.top_k, prune_rate=args.prune)

        res = run_step_with_retries(
            attempt, retries=args.serve_retries, backoff_s=0.05,
            retry_on=(faults.FaultError,), on_retry=lambda i, e: print(f"[serve] retry {i + 1}: {e}"),
            rng=np.random.default_rng(spec.seed))
        # surface the winning attempt's degradation fields in the summary
        # (earlier versions printed them mid-flight and then dropped them)
        res["probe"] = dict(probe_state)
        print(f"[serve] fault counters: reads={counters.reads} "
              f"faults={counters.faults_injected} "
              f"corrected={counters.corrected} votes={counters.votes} "
              f"delays={counters.delays}")
    else:
        res = serve(cfg, args.batch, args.prompt_len, args.max_new,
                    top_k=args.top_k, prune_rate=args.prune)
    summary = (f"[serve] prefill {res['prefill_s']*1e3:.0f}ms, "
               f"decode {res['decode_tok_per_s']:.1f} tok/s, "
               f"prune={res['pruned']:.0%}")
    probe = res.get("probe")
    if probe:
        summary += (f", degraded={probe['degraded']} "
                    f"repairs={probe['repairs']} retries={probe['retries']} "
                    f"quality={probe['quality']:.3f}")
    print(summary)
    print(f"[serve] first sequence: {res['tokens'][0][:24]}...")


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(
        description="continuous-batching serving loop (default) or the "
                    "one-shot model-decode driver (--oneshot)")
    ap.add_argument("--oneshot", action="store_true",
                    help="run the original prefill+decode model driver "
                         "instead of the request-serving loop")
    ap.add_argument("--fault-spec", default=None,
                    help="inject device faults for the whole run, e.g. "
                         "'ber=0.01,banks=4,dead_banks=1:2,seed=0' "
                         "(see repro.runtime.faults.FaultSpec)")
    ap.add_argument("--list-engines", action="store_true",
                    help="print the sort-engine registry and exit")
    # serving-loop knobs
    grp = ap.add_argument_group("serving loop")
    grp.add_argument("--requests", type=int, default=40)
    grp.add_argument("--n", type=int, default=48,
                     help="per-request problem size")
    grp.add_argument("--mean-gap-us", type=float, default=0.1,
                     help="mean inter-arrival gap (device us)")
    grp.add_argument("--seed", type=int, default=0)
    grp.add_argument("--max-batch", type=int, default=8)
    grp.add_argument("--chunk", type=int, default=8,
                     help="emission chunk per orchestrator step")
    grp.add_argument("--quality-floor", type=float, default=None,
                     help="override every request's quality floor "
                          "(defaults to 0.99 under --fault-spec)")
    grp.add_argument("--out", default=None,
                     help="write the summary JSON here")
    # one-shot knobs
    grp = ap.add_argument_group("one-shot model driver")
    grp.add_argument("--arch", default=None)
    grp.add_argument("--batch", type=int, default=4)
    grp.add_argument("--prompt-len", type=int, default=16)
    grp.add_argument("--max-new", type=int, default=32)
    grp.add_argument("--top-k", type=int, default=0)
    grp.add_argument("--prune", type=float, default=0.0)
    grp.add_argument("--layers", type=int, default=4)
    grp.add_argument("--d-model", type=int, default=256)
    grp.add_argument("--vocab", type=int, default=1024)
    grp.add_argument("--full-size", action="store_true")
    grp.add_argument("--router-impl", default=None,
                     choices=sort_engine.TOPK_ENGINES,
                     help="MoE routing top-k engine (default: the arch "
                          "config's choice)")
    grp.add_argument("--serve-retries", type=int, default=2,
                     help="full-run retries when the fault pre-flight "
                          "degrades (with --fault-spec)")
    args = ap.parse_args()
    compile_cache.enable()

    if args.list_engines:
        for name, spec in sorted(sort_engine.engines().items()):
            print(f"{name:12s} [{spec.mode:10s}] {spec.description}")
        return
    if args.oneshot:
        _oneshot_main(args)
        return

    fault_spec = faults.parse_spec(args.fault_spec) if args.fault_spec \
        else None
    floor = args.quality_floor
    if floor is None and fault_spec is not None:
        floor = 0.99   # force verified engines on a faulted array
    report = serve_requests(
        args.requests, n=args.n, seed=args.seed,
        mean_gap_us=args.mean_gap_us, max_batch=args.max_batch,
        chunk=args.chunk, quality_floor=floor, fault_spec=fault_spec)
    _print_report(report)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"[serve] wrote {args.out}")


if __name__ == "__main__":
    main()
