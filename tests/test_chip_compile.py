"""Compile the main path's Pallas kernels, and the full sort's XLA radix
sort, for a described TPU v5e chip.

Nothing runs.  Each test lowers a kernel at a real size with
``interpret=False`` and compiles it with the TPU compiler installed beside
JAX, which refuses what the chip would refuse: a primitive Mosaic cannot
lower, a block not aligned to the (8, 128) tiling, more VMEM than a kernel
may use.  Interpret mode checks none of that.

The topology is described inside a fixture, never at import: only the
process that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import radix_select
from repro.kernels import (bitplane_pack, digit_read, fused_tns,
                           masked_matmul, radix_topk)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs in /tmp
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    mp.undo()


def _fused_tns(B, W, N, m):
    fn = functools.partial(fused_tns._fused_tns_rank, k=2, stop_after=m,
                           interpret=False)
    return fn, [((B, W, N), jnp.uint8)]


CASES = {
    "fused_tns_64x16x1024_m2": _fused_tns(64, 16, 1024, 2),
    "fused_tns_16x16x4096_m1": _fused_tns(16, 16, 4096, 1),
    "radix_topk_512x160_k6": (
        functools.partial(radix_topk.topk_keys, k=6, interpret=False),
        [((512, 160), jnp.uint32)]),
    "digit_read_64x16x1024": (
        functools.partial(digit_read.min_search, interpret=False),
        [((64, 16, 1024), jnp.uint8)]),
    "pack_keys_512x160_f32": (
        functools.partial(bitplane_pack.pack_keys, interpret=False),
        [((512, 160), jnp.float32)]),
    "pruned_matmul_256x512x256_bf16": (
        functools.partial(masked_matmul.pruned_matmul, interpret=False),
        [((256, 512), jnp.bfloat16), ((512, 256), jnp.bfloat16),
         ((512,), jnp.bool_)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args = CASES[case]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_radix_sort_compiles_for_v5e_without_gathers(one_chip):
    """The full-sort cell's shape takes the dense passes: matmuls and
    elementwise work, no gather or scatter left for the chip to run one
    element at a time."""
    x = jax.ShapeDtypeStruct((128, 1024), jnp.uint32, sharding=one_chip)
    hlo = radix_select.radix_sort_keys.lower(x, r=8).compile().as_text()
    assert "convolution" in hlo
    assert "gather(" not in hlo and "scatter(" not in hlo
