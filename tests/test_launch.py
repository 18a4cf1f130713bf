"""Launch-layer tests: sharding policy, mesh construction, and actually
EXECUTING sharded train/decode steps on a forced multi-device host mesh."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import roofline as rl


class TestRooflineParser:
    def test_parse_collectives(self):
        hlo = """
  %ar = bf16[128,256]{1,0} all-reduce(bf16[128,256]{1,0} %x), replica_groups={}
  %ag.1 = f32[64]{0} all-gather(f32[4]{0} %y), dimensions={0}
  %a2a = (s32[8,8]{1,0}, s32[8,8]{1,0}) all-to-all(s32[8,8]{1,0} %a, s32[8,8]{1,0} %b)
  %cp-start = bf16[16]{0} collective-permute-start(bf16[16]{0} %z)
  %cp-done = bf16[16]{0} collective-permute-done(bf16[16]{0} %w)
"""
        out = rl.parse_collectives(hlo)
        assert out["bytes"]["all-reduce"] == 128 * 256 * 2
        assert out["bytes"]["all-gather"] == 64 * 4
        assert out["bytes"]["all-to-all"] == 2 * 8 * 8 * 4
        assert out["counts"]["collective-permute"] == 1   # -done skipped

    def test_roofline_terms_and_bottleneck(self):
        r = rl.Roofline(compute_s=0.1, memory_s=0.2, collective_s=0.05,
                        flops_per_device=1, bytes_per_device=1,
                        coll_bytes_per_device=1, chips=256,
                        model_flops=1e12, useful_ratio=0.5)
        assert r.bottleneck == "memory"
        assert r.step_time_s == 0.2
        assert r.roofline_fraction == pytest.approx(0.5)


class TestShardingPolicy:
    def test_specs_small_mesh(self):
        code = r"""
import sys; sys.path.insert(0, "src")
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.launch import mesh as mesh_lib, sharding as sh
from repro.models import stacked
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = configs.get_config("qwen3_14b")
sds = jax.eval_shape(lambda k: stacked.init_params(cfg, k),
                     jax.random.PRNGKey(0))
specs = sh.param_specs(mesh, sds)
# embed.tok (V, d): vocab 151936 % 4 == 0 -> sharded
assert specs["embed"]["tok"] == P("model", "data"), specs["embed"]["tok"]
# stacked attn wq: (40, d, H*hd) -> leading layer axis unsharded
blk = specs["segments"][0]
assert blk["attn"]["wq"] == P(None, "data", "model")
assert blk["norm1"]["w"] == P(None, None)   # replicated (padded to ndim)
# MoE arch: experts divisible by 4 -> expert parallel
cfg2 = configs.get_config("qwen2_moe_a2_7b")
sds2 = jax.eval_shape(lambda k: stacked.init_params(cfg2, k),
                      jax.random.PRNGKey(0))
specs2 = sh.param_specs(mesh, sds2)
assert specs2["segments"][0]["moe"]["wi"] == P(None, "model", "data", None)
print("OK")
"""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]

    def test_moe_nondivisible_experts_fall_back(self):
        code = r"""
import sys; sys.path.insert(0, "src")
import jax
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.launch import sharding as sh
from repro.models import stacked
mesh = jax.make_mesh((1, 7), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = configs.get_config("qwen2_moe_a2_7b")   # 60 experts % 7 != 0
sds = jax.eval_shape(lambda k: stacked.init_params(cfg, k),
                     jax.random.PRNGKey(0))
specs = sh.param_specs(mesh, sds)
wi = specs["segments"][0]["moe"]["wi"]
assert wi[1] is None, wi      # E not sharded
print("OK")
"""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=7")
        out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]


class TestShardedExecution:
    """Actually RUN sharded steps on an 8-device host mesh and check the
    results equal the single-device computation."""

    def test_train_and_decode_sharded_equal_unsharded(self):
        code = r"""
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import configs
from repro.launch import mesh as mesh_lib, sharding as sh, steps as steps_lib
from repro.models import stacked, shard
from repro.optim import adamw

cfg = configs.get_config("qwen2_moe_a2_7b").reduced()
import dataclasses
cfg = dataclasses.replace(cfg, n_routed_experts=8)
params = stacked.init_params(cfg, jax.random.PRNGKey(0))
ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1)
opt = adamw.init(params, ocfg)
toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)),
                   jnp.int32)
step = steps_lib.make_train_step(cfg, ocfg)
p_ref, _, m_ref = jax.jit(step)(params, opt, toks, toks)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
pspecs, ospecs = sh.param_specs(mesh, params), sh.opt_specs(mesh, opt)
with mesh:
    with shard.mesh_axes(("data",), "model"):
        jitted = jax.jit(step,
            in_shardings=(sh.named(mesh, pspecs), sh.named(mesh, ospecs),
                          sh.named(mesh, sh.batch_spec(mesh, toks.shape, ("data",))),
                          sh.named(mesh, sh.batch_spec(mesh, toks.shape, ("data",)))),
            out_shardings=(sh.named(mesh, pspecs), sh.named(mesh, ospecs), None))
        p_sh, _, m_sh = jitted(params, opt, toks, toks)
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3, \
    (float(m_ref["loss"]), float(m_sh["loss"]))
d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
    a.astype(jnp.float32) - b.astype(jnp.float32)))), p_ref, p_sh)
assert max(jax.tree.leaves(d)) < 2e-2, sorted(jax.tree.leaves(d))[-3:]

# decode step sharded
caches = stacked.init_cache(cfg, 8, 32)
dec = steps_lib.make_decode_step(cfg)
tok = toks[:, :1]; pos = jnp.zeros((8,), jnp.int32)
lg_ref, _ = jax.jit(dec)(params, tok, pos, caches)
cspecs = sh.cache_specs(mesh, caches, ("data",))
with mesh:
    with shard.mesh_axes(("data",), "model"):
        jd = jax.jit(dec, in_shardings=(
            sh.named(mesh, pspecs),
            sh.named(mesh, sh.batch_spec(mesh, tok.shape, ("data",))),
            sh.named(mesh, sh.batch_spec(mesh, pos.shape, ("data",))),
            sh.named(mesh, cspecs)),
            out_shardings=(None, sh.named(mesh, cspecs)))
        lg_sh, _ = jd(params, tok, pos, caches)
err = float(jnp.max(jnp.abs(lg_ref.astype(jnp.float32)
                            - lg_sh.astype(jnp.float32))))
assert err < 2e-2, err
print("OK")
"""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout


def test_mesh_helpers_do_not_touch_devices():
    # mesh.py must be importable without initializing a 512-device backend
    from repro.launch import mesh as mesh_lib
    assert callable(mesh_lib.make_production_mesh)
    assert len(jax.devices()) == 1      # smoke tests still see one device


def test_dryrun_cell_subprocess_smallest():
    """End-to-end dry-run of one small cell in a subprocess (the full 40-
    cell x 2-mesh sweep runs via `python -m repro.launch.dryrun --all`)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "olmo_1b",
         "--shape", "decode_32k", "--out", "/tmp/dryrun_test"],
        cwd="/root/repo", env=dict(os.environ, PYTHONPATH="src"),
        capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "bottleneck=" in out.stdout
