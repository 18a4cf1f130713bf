"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, print memory/cost analyses, extract roofline
terms.  A CPU-only compile tool: it MUST be run as its own process,
because it pins JAX to the CPU and forces 512 host devices before JAX is
imported (on a host with a chip it would otherwise take the chip).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_14b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.models import accounting, shard, stacked
from repro.models.config import ALL_SHAPES, ArchConfig, ShapeConfig, shapes_for
from repro.optim import adamw


def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """ShapeDtypeStruct stand-ins for every model input of this cell —
    weak-type-correct, shardable, no device allocation."""
    dp = mesh_lib.data_axes(mesh)
    B, S = shape.global_batch, shape.seq_len
    sds = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt)
    out = {}
    if shape.kind == "train":
        out["tokens"] = sds((B, S), jnp.int32)
        out["labels"] = sds((B, S), jnp.int32)
    elif shape.kind == "prefill":
        out["tokens"] = sds((B, S), jnp.int32)
    else:  # decode: one new token against a seq_len-deep cache
        out["token"] = sds((B, 1), jnp.int32)
        out["pos"] = sds((B,), jnp.int32)
    if cfg.frontend_tokens:
        out["frontend"] = sds(
            (B, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model),
            cfg.dtype())
    return out


def _accum_for(cfg: ArchConfig, shape: ShapeConfig) -> int:
    """Gradient-accumulation microbatches: bound per-device activation
    memory for the big training cells (v5e has 16 GB HBM)."""
    if shape.kind != "train":
        return 1
    tokens = shape.seq_len * shape.global_batch
    act_cost = tokens * cfg.d_model
    if accounting.param_count(cfg) > 5e10 or act_cost > 2 ** 32:
        return 8
    if act_cost > 2 ** 31:
        return 4
    return 1


def _ssm_chunk_fix(cfg: ArchConfig, shape: ShapeConfig) -> ArchConfig:
    import dataclasses
    if cfg.ssm_state and shape.seq_len % cfg.ssm_chunk != 0:
        return dataclasses.replace(cfg, ssm_chunk=shape.seq_len)
    return cfg


def build_cell(arch: str, shape_name: str, mesh, *, remat: str = "full",
               accum: Optional[int] = None, router_impl: Optional[str] = None,
               attn_impl: Optional[str] = None, serve_params: bool = False,
               unroll: bool = False, depth: Optional[int] = None,
               accum_bf16: bool = False, seq_shard_cache: bool = False):
    """Returns (fn, in_sds tuple, in_shardings tuple, donate) for jit.

    ``serve_params``: TP-only parameter sharding (replicated over the data
    axes) — the serving-mode layout that eliminates per-step FSDP
    all-gathers for decode/prefill cells.
    ``depth``: override n_layers (marginal-layer costing for archs too deep
    to compile unrolled — see tools/marginal_cost.py)."""
    import dataclasses
    cfg = configs.get_config(arch)
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        raise SkipCell(f"{arch} is full-attention: long_500k skipped "
                       "(DESIGN.md §Arch-applicability)")
    cfg = _ssm_chunk_fix(cfg, shape)
    if router_impl:
        cfg = dataclasses.replace(cfg, router_impl=router_impl)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if depth:
        pat = cfg.layer_pattern[:depth] if cfg.layer_pattern else None
        cfg = dataclasses.replace(cfg, n_layers=depth, layer_pattern=pat)
    dp_axes = mesh_lib.data_axes(mesh)
    wf = bool(cfg.frontend_tokens)

    params_sds = jax.eval_shape(
        lambda k: stacked.init_params(cfg, k), jax.random.PRNGKey(0))
    pspecs = sh.param_specs(mesh, params_sds,
                            dp=None if serve_params else "data")
    ins = input_specs(cfg, shape, mesh)

    if shape.kind == "train":
        ocfg = adamw.AdamWConfig()
        opt_sds = jax.eval_shape(lambda p: adamw.init(p, ocfg), params_sds)
        ospecs = sh.opt_specs(mesh, opt_sds)
        acc = accum if accum is not None else _accum_for(cfg, shape)
        import jax.numpy as _jnp
        fn = steps_lib.make_train_step(
            cfg, ocfg, remat=remat, accum=acc, with_frontend=wf,
            unroll=unroll,
            accum_dtype=_jnp.bfloat16 if accum_bf16 else _jnp.float32)
        args = [params_sds, opt_sds, ins["tokens"], ins["labels"]]
        shardings = [pspecs, ospecs,
                     sh.batch_spec(mesh, ins["tokens"].shape, dp_axes),
                     sh.batch_spec(mesh, ins["labels"].shape, dp_axes)]
        out_shardings = (pspecs, ospecs, None)
        donate = (0, 1)
    else:
        cache_sds = jax.eval_shape(
            lambda: stacked.init_cache(cfg, shape.global_batch,
                                       shape.seq_len))
        cspecs = sh.cache_specs(mesh, cache_sds, dp_axes,
                                seq_shard=seq_shard_cache)
        if shape.kind == "prefill":
            fn = steps_lib.make_prefill_step(cfg, with_frontend=wf,
                                             unroll=unroll)
            args = [params_sds, ins["tokens"], cache_sds]
            shardings = [pspecs,
                         sh.batch_spec(mesh, ins["tokens"].shape, dp_axes),
                         cspecs]
            out_shardings = (None, cspecs)
            donate = (2,)
        else:
            fn = steps_lib.make_decode_step(cfg, with_frontend=wf,
                                            unroll=unroll)
            args = [params_sds, ins["token"], ins["pos"], cache_sds]
            shardings = [pspecs,
                         sh.batch_spec(mesh, ins["token"].shape, dp_axes),
                         sh.batch_spec(mesh, ins["pos"].shape, dp_axes),
                         cspecs]
            out_shardings = (None, cspecs)
            donate = (3,)
    if wf:
        args.append(ins["frontend"])
        shardings.append(sh.batch_spec(mesh, ins["frontend"].shape, dp_axes))
    return cfg, shape, fn, tuple(args), tuple(shardings), out_shardings, donate


class SkipCell(Exception):
    pass


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, remat: str = "full",
             accum: Optional[int] = None, router_impl: Optional[str] = None,
             attn_impl: Optional[str] = None, serve_params: bool = False,
             unroll: bool = False, depth=None, accum_bf16: bool = False,
             seq_shard_cache: bool = False, tag: str = "") -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.monotonic()
    cfg, shape, fn, args, in_sh, out_sh, donate = build_cell(
        arch, shape_name, mesh, remat=remat, accum=accum,
        router_impl=router_impl, attn_impl=attn_impl,
        serve_params=serve_params, unroll=unroll, depth=depth,
        accum_bf16=accum_bf16, seq_shard_cache=seq_shard_cache)
    in_named = tuple(sh.named(mesh, s) for s in in_sh)
    out_named = tuple(sh.named(mesh, s) if s is not None else None
                      for s in out_sh)
    with mesh:
        with shard.mesh_axes(mesh_lib.data_axes(mesh), "model", mesh):
            jitted = jax.jit(
                fn,
                in_shardings=in_named,
                out_shardings=out_named,
                donate_argnums=donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    mem = compiled.memory_analysis()
    txt = compiled.as_text()
    roof = rl.analyze(compiled, chips,
                      accounting.model_flops(cfg, shape), hlo_text=txt)
    colls = rl.parse_collectives(txt)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "chips": chips,
        "compile_s": round(compile_s, 1),
        "params_total": accounting.param_count(cfg),
        "params_active": accounting.active_param_count(cfg),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_est_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "collectives": colls,
        "roofline": roof.to_dict(),
        "unroll": unroll,
        "depth": depth,
        "remat": remat,
        "tag": tag,
    }
    print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
          f"compile {compile_s:.0f}s, "
          f"bottleneck={roof.bottleneck}, "
          f"terms(s)=C{roof.compute_s:.4f}/M{roof.memory_s:.4f}/"
          f"X{roof.collective_s:.4f}, "
          f"peak/dev={rec['memory']['peak_est_bytes']/2**30:.2f}GiB")
    print(f"  memory_analysis: {mem}")
    ca = compiled.cost_analysis()
    print(f"  cost_analysis: flops={rl.cost_value(ca, 'flops'):.3e} "
          f"bytes={rl.cost_value(ca, 'bytes accessed'):.3e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape_name}__{mesh_name}{tag}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--router-impl", default=None, choices=["radix", "lax"])
    ap.add_argument("--attn-impl", default=None, choices=["naive", "chunked"])
    ap.add_argument("--serve-params", action="store_true")
    ap.add_argument("--depth", type=int, default=None)
    ap.add_argument("--accum-bf16", action="store_true")
    ap.add_argument("--seq-shard-cache", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans so cost_analysis counts every "
                         "layer (roofline-accurate costing)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in configs.ARCH_IDS:
            for s in shapes_for(configs.get_config(arch)):
                cells.append((arch, s.name))
    else:
        cells.append((args.arch, args.shape))

    failures = []
    for arch, shape_name in cells:
        try:
            run_cell(arch, shape_name, args.multi_pod, args.out,
                     remat=args.remat, accum=args.accum,
                     router_impl=args.router_impl, attn_impl=args.attn_impl,
                     serve_params=args.serve_params, unroll=args.unroll,
                     depth=args.depth, accum_bf16=args.accum_bf16,
                     seq_shard_cache=args.seq_shard_cache, tag=args.tag)
        except SkipCell as e:
            print(f"[dryrun] SKIP {arch} x {shape_name}: {e}")
        except Exception:
            failures.append((arch, shape_name))
            print(f"[dryrun] FAIL {arch} x {shape_name}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"dry-run failures: {failures}")


if __name__ == "__main__":
    main()
