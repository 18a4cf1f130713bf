"""Pallas TPU kernel: fused comparison-free top-k (the MoE router hot-spot).

The kernel runs the paper's min-search loop k times entirely in VMEM/VREGs:
for each of the k extractions it walks the radix-2^r digit planes MSB->LSB
(the multi-level strategy, §2.3.3), maintaining the number-exclusion mask in
vector registers, then excludes the located minimum and repeats.  The min
key is reconstructed from the selected digits, so there is no gather.

Layout: keys are uint32 order-preserving sort keys, shape (B, N).  One grid
program handles a (BM, N) row tile; N stays resident in VMEM (router sizes:
N = #experts <= a few hundred; we pad N to the 128-lane boundary with
0xFFFFFFFF sentinels).  k and r are compile-time constants.

The smallest digit present among the survivors is one masked min-reduction
per digit, with no (BM, N, 2^r) intermediate, keeping the VMEM working set
at O(BM * N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import backend
from repro.kernels.digit_read import pad_lanes, pad_to

KEY_BITS = 32
# NOTE: numpy, not jnp — this module may be lazily imported inside a jit
# trace, and a module-level jnp constant created there would capture (and
# later leak) a tracer
SENTINEL = np.uint32(0xFFFFFFFF)


def _topk_kernel(keys_ref, idx_ref, key_ref, *, k: int, r: int, n_valid: int):
    keys = keys_ref[...]                                   # (BM, N) uint32
    bm, n = keys.shape
    R = 1 << r
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, n), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    valid = lane < n_valid
    idx_out = jnp.zeros((bm, k), jnp.int32)
    key_out = jnp.zeros((bm, k), jnp.uint32)
    for j in range(k):
        m = valid
        min_key = jnp.zeros((bm, 1), dtype=jnp.uint32)
        for shift in range(KEY_BITS - r, -1, -r):
            dig = ((keys >> shift) & (R - 1)).astype(jnp.int32)
            # smallest digit present among the survivors: DR + the
            # "all 0's/1's" periphery, one lane reduction per digit
            dmin = jnp.min(jnp.where(m, dig, R), axis=1, keepdims=True)
            m = m & (dig == dmin)                          # number exclusion
            min_key = min_key | (dmin.astype(jnp.uint32) << shift)
        # first of ties: the lowest surviving lane
        chosen = jnp.min(jnp.where(m, lane, n), axis=1, keepdims=True)
        idx_out = jnp.where(col == j, chosen, idx_out)
        key_out = jnp.where(col == j, min_key, key_out)
        valid = valid & (lane != chosen)
    idx_ref[...] = idx_out
    key_ref[...] = key_out


@functools.partial(jax.jit, static_argnames=("k", "r", "block_rows",
                                             "interpret"))
def topk_keys(keys: jnp.ndarray, k: int, r: int = 4, block_rows: int = 8,
              interpret: bool | None = None):
    """(min_keys, indices) of the k smallest along the last axis (ascending
    emission), for uint32 keys of shape (B, N).  ``interpret=None``
    resolves per backend."""
    interpret = backend.use_interpret(interpret)
    assert keys.dtype == jnp.uint32 and keys.ndim == 2
    b, n = keys.shape
    n_pad = pad_lanes(n)
    bm = min(block_rows, b)
    b_pad = -(-b // bm) * bm
    keys_p = pad_to(keys, (b_pad, n_pad), SENTINEL)
    grid = (b_pad // bm,)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, r=r, n_valid=n),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, n_pad), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b_pad, k), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, k), jnp.uint32)],
        interpret=interpret,
    )(keys_p)
    idx, mkeys = out
    return mkeys[:b], idx[:b]
