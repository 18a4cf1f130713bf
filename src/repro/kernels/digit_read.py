"""Pallas TPU kernel: one digit-read min-search over raw bit-planes.

This is the paper's periphery (sense amplifiers + all-0's/1's check + number
exclusion, Fig. 3a / S7) for a complete min/max-search iteration, fused into
a single kernel.  Input is the physical array image: (B, W, N) uint8 bit
planes, MSB first — exactly what ``bitplane.to_bitplanes`` programs.  The
kernel walks the W planes with the NE mask in vector registers and returns

* the min/max mask (ties included — the "survival numbers"), and
* the number of *useful* DRs (mixed reads, i.e. reads that caused a number
  exclusion) — the quantity TNS tries to minimize.

The wrapper packs each lane's digit column into one int32 word; a grid
program walks the planes of 8 batch rows with their (8, N) word tile in
VMEM (N<=64k => 2 MB of int32, inside the 16 MB VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def pad_lanes(n: int) -> int:
    """Smallest multiple of the 128-wide TPU lane tile that covers ``n``."""
    return max(128, -(-n // 128) * 128)


def pad_to(x: jnp.ndarray, shape, fill) -> jnp.ndarray:
    """Pad the trailing edge of every axis of ``x`` up to ``shape`` with a
    constant ``fill`` — ONE ``jnp.pad`` call, so one buffer materializes
    (vs the zero-alloc + two ``.at[].set`` copies it replaces).  Shared by
    every Pallas kernel entry point that lane-pads its operands."""
    cfg = tuple((0, t - s) for s, t in zip(x.shape, shape))
    if not any(hi for _, hi in cfg):
        return x
    return jnp.pad(x, cfg, constant_values=fill)


def pack_columns(planes: jnp.ndarray) -> jnp.ndarray:
    """(B, W, N) 0/1 bit planes -> (B, N) int32: each lane's digit column
    packed into one word, column 0 in the most significant of its W bits
    (W <= 32).  The kernels read the array image in this form — one 32-bit
    lane per number instead of W uint8 sublanes, which Mosaic cannot slice."""
    planes = (planes != 0).astype(jnp.int32)
    word = planes[:, 0, :]
    for c in range(1, planes.shape[1]):
        word = (word << 1) | planes[:, c, :]
    return word


def _dr_kernel(word_ref, mask_ref, drs_ref, *, width: int, n_valid: int,
               ascending: bool):
    words = word_ref[...]                        # (bm, N) packed columns
    bm, n = words.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    # the survivor mask stays int32 0/1: Mosaic cannot select between
    # bool vectors
    valid = (lane < n_valid).astype(jnp.int32)
    useful = jnp.zeros((bm, 1), dtype=jnp.int32)
    exc = 1 if ascending else 0
    for col in range(width):
        row = (words >> (width - 1 - col)) & 1
        hit = jnp.where(row == exc, valid, 0)
        keep = valid - hit
        mixed = (jnp.max(hit, axis=1, keepdims=True)
                 & jnp.max(keep, axis=1, keepdims=True))   # (bm, 1) 0/1
        valid = jnp.where(mixed != 0, keep, valid)
        useful = useful + mixed
    mask_ref[...] = valid
    drs_ref[...] = useful


@functools.partial(jax.jit, static_argnames=("ascending", "interpret"))
def min_search(planes: jnp.ndarray, ascending: bool = True,
               interpret: bool | None = None):
    """(min_mask, useful_drs) for batched bit-planes (B, W, N) uint8.

    ``min_mask[b]`` marks every element attaining the min (max when
    ``ascending=False``) — the survival numbers of one search iteration.
    ``interpret=None`` resolves per backend (compiled on TPU, interpret
    on CPU)."""
    interpret = backend.use_interpret(interpret)
    assert planes.ndim == 3 and planes.dtype == jnp.uint8
    b, w, n = planes.shape
    assert w <= 32, "a lane's digit column is packed into one 32-bit word"
    n_pad = pad_lanes(n)
    bm = min(b, 8)                  # whole 8-row sublane tiles per program
    b_pad = -(-b // bm) * bm
    # padded lanes sit outside the kernel's `valid` mask, so their fill
    # never takes part in a search
    words = pad_to(pack_columns(planes), (b_pad, n_pad), 0)
    mask, drs = pl.pallas_call(
        functools.partial(_dr_kernel, width=w, n_valid=n, ascending=ascending),
        grid=(b_pad // bm,),
        in_specs=[pl.BlockSpec((bm, n_pad), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, n_pad), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b_pad, n_pad), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, 1), jnp.int32)],
        interpret=interpret,
    )(words)
    return mask[:b, :n] != 0, drs[:b, 0]
