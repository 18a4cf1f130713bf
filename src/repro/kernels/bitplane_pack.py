"""Pallas TPU kernel: order-preserving sort-key packing (VPU elementwise).

Converts float32 / bfloat16 / int32 tensors into unsigned keys whose
integer order equals the value order (IEEE trick: negative values flip all
bits, non-negatives flip the sign bit) — the "programming" transform the
throughput-mode engines consume.  Blocked elementwise: (BM, BN) VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _pack_f32_kernel(x_ref, o_ref):
    x = x_ref[...]
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = u >> 31
    o_ref[...] = jnp.where(sign == 1, ~u, u ^ jnp.uint32(0x80000000))


def _unpack_f32_kernel(k_ref, o_ref):
    key = k_ref[...]
    sign = key >> 31
    u = jnp.where(sign == 0, ~key, key ^ jnp.uint32(0x80000000))
    o_ref[...] = jax.lax.bitcast_convert_type(u, jnp.float32)


def _pack_i32_kernel(x_ref, o_ref):
    x = x_ref[...]
    o_ref[...] = jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(0x80000000)


def _blocked_elementwise(kernel, x, out_dtype, *, interpret: bool,
                         block=(256, 512)):
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    bn = block[0] * block[1]
    n_pad = -(-n // bn) * bn
    flat = jnp.pad(flat, (0, n_pad - n))
    x2 = flat.reshape(n_pad // block[1], block[1])
    grid = (x2.shape[0] // block[0],)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, out_dtype),
        interpret=interpret,
    )(x2)
    return out.reshape(-1)[:n].reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_keys(x: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """Order-preserving uint32 keys for float32/bfloat16/int32 input."""
    interpret = backend.use_interpret(interpret)
    if x.dtype == jnp.bfloat16:
        x = x.astype(jnp.float32)          # bf16 embeds exactly in f32
    if x.dtype == jnp.float32:
        return _blocked_elementwise(_pack_f32_kernel, x, jnp.uint32,
                                    interpret=interpret)
    if x.dtype == jnp.int32:
        return _blocked_elementwise(_pack_i32_kernel, x, jnp.uint32,
                                    interpret=interpret)
    if x.dtype == jnp.uint32:
        return x
    raise ValueError(f"unsupported dtype {x.dtype}")


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_keys_f32(keys: jnp.ndarray,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Inverse of ``pack_keys`` for float32."""
    return _blocked_elementwise(_unpack_f32_kernel, keys, jnp.float32,
                                interpret=backend.use_interpret(interpret))
