"""Throughput-mode comparison-free selection: the TPU-native adaptation of
the paper's digit-read machinery.

The cycle-faithful engine (core/tns.py) executes the paper's controller one
DR at a time — correct for latency/energy studies, but serial.  On a TPU the
same *insight* (min/max/top-k located by digit-plane masking, never by
pairwise compare-and-swap) vectorizes:

* a digit read over radix-2^r digits == extracting a digit slice of the
  order-preserving sort key (the multi-level strategy, §2.3.3, generalized);
* the number-exclusion register == a boolean lane mask in VREGs;
* the "all 0's / all 1's periphery" == presence/histogram reductions, which
  map onto the MXU as one-hot matmuls for large N.

Three primitives, all jittable/vmappable and batched over leading dims:

* ``min_mask`` / ``extract_topk``: exact top-k with indices via iterated
  digit-plane min-search — the paper's min-search loop, vectorized.  Used
  by MoE routers (k<=8, N<=256).
* ``topk_threshold_mask``: histogram radix-select producing the top-k mask
  (threshold + partial ties) without materializing indices — used for
  logit top-k sampling and in-situ pruning over vocab-sized axes.
* ``radix_sort_keys``: full LSB-first counting radix sort (stable),
  comparison-free — used to order tokens by expert in the MoE dispatch.
  Short rows rank and reorder each pass as dense one-hot matmuls; long
  rows by gathers and a scatter.

All take *unsigned keys* from bitplane.sort_key_jnp; wrappers handle floats.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import bitplane as bp


def _key_width(keys: jnp.ndarray) -> int:
    if keys.dtype == jnp.uint8:
        return 8
    if keys.dtype == jnp.uint16:
        return 16
    if keys.dtype == jnp.uint32:
        return 32
    raise ValueError(f"keys must be uint8/16/32, got {keys.dtype}")


def _digit(keys: jnp.ndarray, shift: int, r: int) -> jnp.ndarray:
    return ((keys >> shift) & ((1 << r) - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Exact small-N top-k by iterated digit-plane min search (router path).
# ---------------------------------------------------------------------------


def min_mask(keys: jnp.ndarray, valid: jnp.ndarray, r: int = 4) -> jnp.ndarray:
    """Mask of elements equal to min(keys[valid]) on the last axis.

    This is one full min-search of the paper (MSB->LSB digit reads with
    number exclusion), vectorized over leading dims; ``r`` is the
    multi-level cell width."""
    w = _key_width(keys)
    assert w % r == 0
    vals = jnp.arange(1 << r, dtype=jnp.int32)
    for shift in range(w - r, -1, -r):
        dig = _digit(keys, shift, r)
        # presence[v] = any(valid & dig==v): the DR + all-0s/1s periphery
        eq = dig[..., None] == vals                      # (..., N, R)
        presence = jnp.any(valid[..., None] & eq, axis=-2)  # (..., R)
        dmin = jnp.argmax(presence, axis=-1).astype(jnp.int32)  # first present
        valid = valid & (dig == dmin[..., None])
    return valid


def extract_topk(keys: jnp.ndarray, k: int, r: int = 4
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact (keys, indices) of the k smallest along the last axis, emitted
    in ascending order — iterated comparison-free min search.  Static k."""
    n = keys.shape[-1]
    valid = jnp.ones(keys.shape, dtype=bool)
    idxs = []
    for _ in range(k):
        m = min_mask(keys, valid, r=r)
        chosen = jnp.argmax(m, axis=-1).astype(jnp.int32)   # first of ties
        idxs.append(chosen)
        valid = valid & (jnp.arange(n) != chosen[..., None])
    idx = jnp.stack(idxs, axis=-1)
    vals = jnp.take_along_axis(keys, idx.astype(jnp.int32), axis=-1)
    return vals, idx


def topk_values(x: jnp.ndarray, k: int, r: int = 4,
                largest: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``jax.lax.top_k``-compatible comparison-free top-k (values desc)."""
    keys = bp.sort_key_jnp(x)
    if largest:
        keys = ~keys
    kv, idx = extract_topk(keys, k, r=r)
    vals = jnp.take_along_axis(x, idx, axis=-1)
    return vals, idx


# ---------------------------------------------------------------------------
# Histogram radix-select threshold mask (vocab-scale path).
# ---------------------------------------------------------------------------


def topk_threshold_mask(keys: jnp.ndarray, k, r: int = 8,
                        smallest: bool = True) -> jnp.ndarray:
    """Boolean mask selecting exactly k elements: all strictly better than
    the threshold key plus the first ties in index order.  ``k`` may be a
    traced scalar (run-time tunable sparsity, §3.2).  O(W/r) histogram
    passes; the histogram is MXU-friendly (one-hot reduction)."""
    if not smallest:
        keys = ~keys
    w = _key_width(keys)
    assert w % r == 0
    R = 1 << r
    vals = jnp.arange(R, dtype=jnp.int32)
    cand = jnp.ones(keys.shape, dtype=bool)       # == threshold prefix so far
    below = jnp.zeros(keys.shape, dtype=bool)     # strictly below threshold
    confirmed = jnp.zeros(keys.shape[:-1], dtype=jnp.int32)
    k_arr = jnp.asarray(k, dtype=jnp.int32)
    for shift in range(w - r, -1, -r):
        dig = _digit(keys, shift, r)
        eq = dig[..., None] == vals                            # (..., N, R)
        hist = jnp.sum((cand[..., None] & eq).astype(jnp.int32), axis=-2)
        cum = jnp.cumsum(hist, axis=-1)                        # inclusive
        ge = (confirmed[..., None] + cum) >= k_arr[..., None]
        t = jnp.argmax(ge, axis=-1).astype(jnp.int32)          # threshold digit
        cum_before = jnp.where(
            t > 0,
            jnp.take_along_axis(cum, jnp.maximum(t - 1, 0)[..., None],
                                axis=-1)[..., 0],
            0)
        confirmed = confirmed + cum_before
        below = below | (cand & (dig < t[..., None]))
        cand = cand & (dig == t[..., None])
    # ties: first (k - confirmed) candidates in index order
    tie_rank = jnp.cumsum(cand.astype(jnp.int32), axis=-1)
    need = (k_arr - confirmed)[..., None]
    mask = below | (cand & (tie_rank <= need))
    return mask


def prune_smallest_mask(x: jnp.ndarray, k, r: int = 8) -> jnp.ndarray:
    """In-situ pruning mask (§3.2): True for the k smallest |x| along the
    last axis — the weights TNS would locate and discard."""
    keys = bp.sort_key_jnp(jnp.abs(x))
    return topk_threshold_mask(keys, k, r=r, smallest=True)


def topk_logits_mask(logits: jnp.ndarray, k, r: int = 8) -> jnp.ndarray:
    """True for the k largest logits (decode-time top-k sampling filter)."""
    keys = bp.sort_key_jnp(logits)
    return topk_threshold_mask(keys, k, r=r, smallest=False)


# ---------------------------------------------------------------------------
# Full comparison-free radix sort (stable, LSB-first counting passes).
# ---------------------------------------------------------------------------


def exclusive_prefix(x: jnp.ndarray) -> jnp.ndarray:
    """Exclusive prefix sum of 0/1 counts along the lanes of a (b, Np)
    tile, Np a multiple of 128.  Each 128-lane chunk takes its in-chunk
    prefix from one matmul with a strict upper-triangular 0/1 matrix (exact
    in f32; the MXU does it), plus the running total of earlier chunks.
    No ``cumsum``: Mosaic does not lower it along the lane axis."""
    b, n = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    tri = (row < col).astype(jnp.float32)          # tri[i, j]: i < j
    base = jnp.zeros((b, 1), jnp.float32)
    parts = []
    for c in range(0, n, 128):
        xc = x[:, c:c + 128].astype(jnp.float32)
        parts.append(jnp.dot(xc, tri, preferred_element_type=jnp.float32)
                     + base)
        base = base + jnp.sum(xc, axis=1, keepdims=True)
    return jnp.concatenate(parts, axis=1).astype(x.dtype)


# Rows of at most this many keys take the dense passes (``_dense_sort``),
# whose work a row grows as N^2 / 128.  On one TPU v5e, at 131,072 keys a
# call, they took 0.26-0.90 ms against the gather passes' 31-38 ms at
# every row length measured, 1,024 to 8,192.
DENSE_MAX_N = 8192
# Digit width of a dense pass.  An r-bit digit's stable counting pass
# equals r/4 stable passes over its 4-bit sub-digits, low first: the
# one-hot and its prefix shrink 2^(r-4)-fold for r/4 times the passes.
# 32-bit keys in 128 rows of 1,024 on one TPU v5e: 2-bit digits 0.43 ms,
# 4-bit 0.26 ms, 8-bit 2.02 ms.
DENSE_DIGIT_BITS = 4


def takes_dense_path(n: int) -> bool:
    """Whether ``radix_sort_keys`` sorts rows of ``n`` keys by dense
    one-hot passes (matmuls) rather than gathers and a scatter."""
    return n <= DENSE_MAX_N


def _bytes(v: jnp.ndarray, nbytes: int) -> list:
    return [(v >> (8 * i)) & 0xFF for i in range(nbytes)]


def _join(cols: jnp.ndarray) -> jnp.ndarray:
    """(B, k, Np) int32 bytes, least significant first -> (B, Np)."""
    return sum(cols[:, i] << (8 * i) for i in range(cols.shape[1]))


def _dense_pass(dig: jnp.ndarray, payload: jnp.ndarray, bins: int
                ) -> jnp.ndarray:
    """One stable counting pass over (B, Np) rows as dense work.

    The slot of key i is ``pos_i`` = the first slot of its bin's keys in
    its 128-key chunk + its rank among them, from the 0/1 one-hot of the
    digits.  The rank is the one-hot's exclusive prefix within the chunk
    (``exclusive_prefix``: one triangular matmul, in index order, hence
    stable); the first slots come from the chunks' bin counts (the
    histogram's exclusive prefix over bins, plus the bin's count in
    earlier chunks); each key's own bin is picked by masking with its
    one-hot and summing over bins.

    The (B, k, Np) byte ``payload`` then moves to its slots by one matmul
    with the one-hot permutation ``P[j, i] = (pos_i == j)``, factored over
    slot j = 128 c + l into the block test ``pos_i >> 7 == c`` (applied to
    the payload) and the lane one-hot ``pos_i & 127 == l``.  Bytes are
    exact in bf16, and each output sums one product."""
    b, n = dig.shape
    oh = (dig[:, None, :] == jnp.arange(bins, dtype=jnp.int32)[:, None]
          ).reshape(b, bins, n // 128, 128)
    rank = exclusive_prefix(oh.reshape(-1, 128).astype(jnp.float32)
                            ).reshape(oh.shape)
    count = rank[..., -1] + oh[..., -1]                 # (B, bins, chunks)
    through = jnp.cumsum(count, axis=-1)
    hist = through[..., -1]                             # (B, bins)
    first = (jnp.cumsum(hist, axis=-1) - hist)[..., None] + through - count
    pos = jnp.sum(jnp.where(oh, rank + first[..., None], 0.0), axis=1)
    pos = pos.reshape(b, n).astype(jnp.int32)
    blk = (pos >> 7)[:, None, :] == jnp.arange(n // 128)[:, None]
    lane = ((pos & 127)[:, None, :] == jnp.arange(128)[:, None]
            ).astype(jnp.bfloat16)                      # (B, 128, Np)
    src = jnp.where(blk[:, None], payload[:, :, None, :], 0
                    ).astype(jnp.bfloat16)              # (B, k, chunks, Np)
    moved = jnp.einsum("bkci,bli->bkcl", src, lane,
                       preferred_element_type=jnp.float32)
    return moved.reshape(b, payload.shape[1], n).astype(jnp.int32)


def _dense_sort(keys: jnp.ndarray, d: int, w: int) -> jnp.ndarray:
    """LSB-first ``_dense_pass``es over d-bit digits, on rows padded to a
    multiple of 128.  Padding keys are the largest key and come last in
    index order, so every stable pass leaves them last.  The payload is
    the key bits still to read and the index, as bytes."""
    n = keys.shape[-1]
    npad = max(128, -(-n // 128) * 128)
    rows = keys.reshape(math.prod(keys.shape[:-1]), n)
    top = jnp.full((rows.shape[0], npad - n), jnp.iinfo(keys.dtype).max,
                   keys.dtype)
    rest = jnp.concatenate([rows, top], axis=1).astype(jnp.uint32)
    perm = jnp.broadcast_to(jnp.arange(npad, dtype=jnp.int32), rest.shape)
    idx_bytes = -(-(npad - 1).bit_length() // 8)
    for shift in range(0, w, d):
        dig = (rest & ((1 << d) - 1)).astype(jnp.int32)
        rest = (rest >> d).astype(jnp.int32)
        key_bytes = -(-(w - shift - d) // 8)
        cols = _bytes(rest, key_bytes) + _bytes(perm, idx_bytes)
        moved = _dense_pass(dig, jnp.stack(cols, axis=1), 1 << d)
        rest = _join(moved[:, :key_bytes])
        perm = _join(moved[:, key_bytes:])
    return perm[:, :n].reshape(keys.shape)


@functools.partial(jax.jit, static_argnames=("r", "descending"))
def radix_sort_keys(keys: jnp.ndarray, r: int = 4,
                    descending: bool = False) -> jnp.ndarray:
    """Permutation sorting ``keys`` ascending along the last axis; stable.
    Counting sort per radix-2^r digit.  Rows of at most ``DENSE_MAX_N``
    keys take dense one-hot passes (``_dense_sort``); longer rows take
    ranks from per-digit cumsums (scatter-free gather formulation)."""
    w = _key_width(keys)
    assert w % r == 0
    if takes_dense_path(keys.shape[-1]):
        perm = _dense_sort(keys, min(r, DENSE_DIGIT_BITS), w)
        return jnp.flip(perm, axis=-1) if descending else perm
    R = 1 << r
    vals = jnp.arange(R, dtype=jnp.int32)
    n = keys.shape[-1]
    perm = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), keys.shape)
    cur = keys
    for shift in range(0, w, r):
        dig = _digit(cur, shift, r)
        eq = dig[..., None] == vals                           # (..., N, R)
        within = jnp.cumsum(eq.astype(jnp.int32), axis=-2)    # rank within bin
        hist = within[..., -1, :]                             # (..., R)
        offs = jnp.concatenate(
            [jnp.zeros_like(hist[..., :1]),
             jnp.cumsum(hist, axis=-1)[..., :-1]], axis=-1)   # exclusive
        pos = (jnp.take_along_axis(
                   offs[..., None, :], dig[..., None], axis=-1)[..., 0]
               + jnp.take_along_axis(within, dig[..., None], axis=-1)[..., 0]
               - 1)
        # gather formulation: new[j] = old[argsort-free inverse]
        inv = jnp.zeros(keys.shape, dtype=jnp.int32)
        inv = jnp.put_along_axis(inv, pos, jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32), keys.shape), axis=-1,
            inplace=False)
        cur = jnp.take_along_axis(cur, inv, axis=-1)
        perm = jnp.take_along_axis(perm, inv, axis=-1)
    if descending:
        return jnp.flip(perm, axis=-1)
    return perm


def sort_values(x: jnp.ndarray, r: int = 4,
                descending: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sorted values, permutation) along the last axis, comparison-free."""
    keys = bp.sort_key_jnp(x)
    perm = radix_sort_keys(keys, r=r, descending=descending)
    return jnp.take_along_axis(x, perm, axis=-1), perm
