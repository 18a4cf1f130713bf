"""Fused Pallas TNS pipeline: digit read -> tree-node-skipping descent ->
winner write-back, all inside ONE ``pl.pallas_call``.

The cycle-faithful machines in :mod:`repro.core.tns` interpret the paper's
controller one cycle per ``while_loop`` trip — every digit decision is a
round-trip through the (dynamically bounded) loop carry.  This kernel
keeps the whole array image resident in VMEM — each lane's W-bit digit
column packed into one int32 word by the wrapper (MSB = column 0, the
sign plane at bit 30) — and replays the SAME controller at
*emission-episode* granularity with a statically structured loop, so it
compiles to straight-line vector code on TPU and to a short fori_loop on
CPU interpret mode.

Episode model (mechanically equivalent to ``core/tns.py``; parity of the
permutation AND of all three observables — cycles, DRs, redundant reload
cycles — is asserted in tests/test_fused_tns.py):

* The k-LIFO only ever holds branch nodes at strictly increasing digit
  columns, push order equals column order (every push happens at a column
  deeper than everything already present), and all present nodes lie on
  ONE root path.  A node's stored mask is recoverable from that path:
  ``stored & alive == prefix_match(path[0..c-1]) & alive`` (an element
  matching the prefix but absent from the stored mask was emitted before
  the push, so it is not alive either).  The whole LIFO therefore
  collapses to a (W,)-bit *digit path* plus per-column ``present`` flags
  — no (k, N) mask planes in the loop carry.  One wrinkle: the machine
  resumes with the PRE-exclusion set, so a resumed column stops filtering
  for everything pushed below it — a per-column ``skip`` flag marks these
  prefix holes (set on resume, cleared when a later descent reads the
  column again).  Drop-oldest at capacity k =
  clear the SHALLOWEST present column; pop = resume the DEEPEST present
  column still matched by an alive element (nodes drained above it pop
  one per controller cycle — ``max(0, d-1)`` of those cycles are the
  paper's redundant reload cycles).  A live resumed node stays present,
  exactly like the hardware LIFO.
* One *episode* = reload + descent + emission.  With each lane's digit
  column one W-bit integer key, the whole descent is closed-form integer
  arithmetic: the machine keeps digit
  ``~exc`` at every split, hence its winner tie-set is the argmin of
  ``key ^ flip`` over the resumed set (``flip`` = kept-digit word,
  prefix holes masked out of the comparison), the DR count is the span
  from the resume column to the deepest column with two contenders
  left, and the mixed-read/push columns are the divergence bits
  (first set bit of ``key XOR winner``) of the losers.  Survivor sets
  that reach the LSB drain as ties — first tie in the LSB read cycle,
  the rest one per repeat cycle — which the episode emits as a whole
  set with consecutive ranks in array-index order (the machine's
  argmax-first order).
* Every running episode emits at least one number, so ``stop_after``
  emissions need at most ``stop_after`` episodes — the static trip count.

The kernel writes an inverse-permutation ring (rank[i] = emission slot
of element i) plus per-instance counters.  The jit around it packs what
the host needs into ONE int32 array, read back once: the four counter
lanes, then, for ``stop_after`` <= ``DEVICE_PERM_MAX``, the permutation's
first slots (a compare and max-reduce over the ring on the device), and
otherwise the whole ring, which the host inverts with numpy.
``level_bits > 1`` stays on the while_loop machine (EngineUnsupported
here, same restriction as the packed fast path).

Dispatch: compiled on TPU/GPU, ``interpret`` on CPU, and under
``REPRO_PALLAS=jnp`` the oracle path reuses ``tns_sort_planes_batched``
itself so parity is testable everywhere (:mod:`repro.kernels.backend`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitplane as bp
from repro.core.radix_select import exclusive_prefix
from repro.kernels import backend
from repro.kernels.digit_read import pack_columns, pad_lanes, pad_to
from repro.runtime import spans


class FusedOut(NamedTuple):
    perm: jnp.ndarray           # (B, stop_n) int32 emission order ((B, N),
                                # -1 pad, from fused_tns_planes)
    cycles: jnp.ndarray         # (B,) int32 controller cycles
    drs: jnp.ndarray            # (B,) int32 digit reads (all)
    reload_cycles: jnp.ndarray  # (B,) int32 redundant reload cycles
    useful_drs: jnp.ndarray     # (B,) int32 mixed reads (caused exclusion)


# counter columns written by the kernel; the first _NOUT lead the packed
# output of _fused_tns_rank
_CYC, _DRS, _RLC, _UDR, _OUT = range(5)
_NOUT = 4
_NCNT = 8          # counter block padded to 8 lanes
_SIGN_BIT = 30     # sign plane's bit in a lane's packed word
# Largest stop_n whose permutation slots are found on the device (the
# top-m regime: serving's m range and pallas-topk's limit).  The search
# compares every lane with every slot, B * stop_n * N work; above this the
# ring goes to the host, where one scatter inverts it.
DEVICE_PERM_MAX = 32


def _flip_mask(fmt: str, ascending: bool, width: int, neg_pend):
    """Per-instance XOR mask turning the W-bit digit word into a key whose
    integer minimum is the machine's descent winner.  Bit ``W-1-c`` is the
    KEPT digit at column ``c`` — the complement of
    ``core.tns._exclude_value`` — so the winner takes flipped-bit 0 at
    every split, i.e. the kept branch.  ``neg_pend`` is the per-instance
    sign-pending vector (constant within an episode: exclusion polarity
    depends only on ``alive``, which emissions change between episodes)."""
    msb = 1 << (width - 1)
    low = msb - 1
    if fmt == bp.UNSIGNED:
        v = 0 if ascending else (msb | low)
        return jnp.full(neg_pend.shape, v, jnp.int32)
    if fmt == bp.TWOS:
        v = msb if ascending else low
        return jnp.full(neg_pend.shape, v, jnp.int32)
    # sign-magnitude / float: sign column is static, the magnitude
    # columns track whether sign-pending numbers are still alive
    base = msb if ascending else 0
    return jnp.where(neg_pend, base | low, base).astype(jnp.int32)


def _bitlength(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Bit length of non-negative ``x`` (0 -> 0).  For width <= 24 the f32
    exponent gives it in O(1) vector ops (exact: x < 2^24); wider words
    fall back to a shift-or smear + popcount."""
    if width <= 24:
        f = x.astype(jnp.float32)
        e = (jax.lax.bitcast_convert_type(f, jnp.int32) >> 23) & 0xFF
        return jnp.where(x == 0, 0, e - 126)
    sm = x
    for sh in (1, 2, 4, 8, 16):
        sm = sm | (sm >> sh)
    return jax.lax.population_count(sm)


def _lane_or(x: jnp.ndarray) -> jnp.ndarray:
    """Bitwise OR across the lanes of a (bm, Np) int32 tile (Np a multiple
    of 128) as a (bm, 1) column: OR the 128-lane chunks together, then a
    log-step rotate-and-OR leaves the total in every lane of the tile."""
    acc = x[:, :128]
    for c in range(128, x.shape[1], 128):
        acc = acc | x[:, c:c + 128]
    for sh in (64, 32, 16, 8, 4, 2, 1):
        acc = acc | pltpu.roll(acc, sh, 1)
    return jnp.max(acc, axis=1, keepdims=True)


def _lowest_bits(x: jnp.ndarray, k: int, width: int) -> jnp.ndarray:
    """The ``k`` lowest set bits of each word of ``x``."""
    keep = jnp.zeros_like(x)
    for _ in range(min(k, width)):
        low = x & -x
        keep = keep | low
        x = x ^ low
    return keep


def _fused_tns_kernel(word_ref, rank_ref, cnt_ref, *,
                      width: int, n_valid: int, k: int, fmt: str,
                      ascending: bool, stop_n: int, unroll: int):
    # Per-instance state lives in (bm, 1) int32 columns, and the LIFO's
    # per-column flags in W-bit words (bit W-1-c = column c), so every
    # value in the loop is a 2-D 32-bit vector Mosaic can lay out.
    words = word_ref[...]                          # (bm, Np) int32
    bm, Np = words.shape
    W = width
    wmask = (1 << W) - 1
    key = words & wmask          # lane's digit column, MSB = column 0
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, Np), 1)
    valid = lane < n_valid
    signed = fmt in (bp.SIGNMAG, bp.FLOAT)
    if signed:
        sign = ((words >> _SIGN_BIT) & 1) != 0     # (bm, Np) bool
        sign_dir = sign if ascending else ~sign
    imax = jnp.iinfo(jnp.int32).max    # sentinel above any masked key

    def episode(carry):
        pathv, skipv, present, rank, out, cyc, drs, rlc, udr = carry
        alive = valid & (rank < 0)
        running = out < stop_n                                   # (bm, 1)

        # ---- reload: pop drained nodes, resume the deepest live one.
        # A node at column c is live iff some alive element matches the
        # current path through column c-1 (holes at `skip` columns match
        # anything): lane match depth = leading agreement of key with the
        # path word, holes masked out.
        if k > 0:
            md = (key ^ pathv) & (~skipv & wmask)
            depth = W - _bitlength(md, W)
            c_max = jnp.max(jnp.where(alive, depth, 0), axis=1,
                            keepdims=True)
            # present columns c <= c_max, i.e. bits >= W-1-c_max
            live = present & ~((1 << jnp.maximum(W - 1 - c_max, 0)) - 1)
            # the deepest live column is the lowest set bit (-1: none)
            c_res = jnp.where(live == 0, -1, W - _bitlength(live & -live, W))
            pos_res = W - 1 - c_res                # c_res == -1 -> W
            below = (1 << pos_res) - 1             # columns deeper than c_res
            d = jax.lax.population_count(present & below)
            spent = jnp.where(running, jnp.maximum(d - 1, 0), 0)
            present = jnp.where(running, present & ~below, present)
            m0 = alive & (depth >= c_res)
            # the resumed column holds the PRE-exclusion set: it stops
            # filtering (a prefix hole) until a later descent re-reads it.
            # Holes above c_res belong to popped subtrees — drop them so
            # the masked comparison below sees those columns again.
            resume = jnp.where(c_res >= 0, 1 << pos_res, 0)
            skipv = jnp.where(running, (skipv & ~below) | resume, skipv)
            col0 = c_res + 1            # restart (c_res == -1) -> column 0
            cyc = cyc + spent
            rlc = rlc + spent
        else:
            col0 = jnp.zeros((bm, 1), jnp.int32)
            m0 = alive

        # ---- descent: the machine reads columns col0.. while >1 valid
        # number remains, keeping digit ~exc at every split — i.e. the
        # winner tie-set is the argmin of key^flip over the resumed set,
        # compared only at non-hole columns.  Per-contender divergence
        # depths (first set bit of XOR vs the winner) replay the DR /
        # mixed-read / push sequence without walking the columns.
        if signed:
            neg_pend = jnp.max(jnp.where(alive & sign_dir, 1, 0), axis=1,
                               keepdims=True) > 0
        else:
            neg_pend = jnp.zeros((bm, 1), dtype=bool)
        flipv = _flip_mask(fmt, ascending, W, neg_pend)
        cmask = (~skipv & wmask) if k > 0 else wmask
        ckey = jnp.where(m0, (key ^ flipv) & cmask, imax)
        kmin = jnp.min(ckey, axis=1, keepdims=True)
        isw = ckey == kmin                         # winner tie-set
        t = jnp.sum(isw.astype(jnp.int32), axis=1, keepdims=True)
        bl = _bitlength(ckey ^ kmin, W)            # 0 for winners
        loser = m0 & ~isw
        # deepest column still read = last with >=2 contenders left: W-1
        # when the winner itself is a tie, else the deepest divergence
        dm = jnp.max(jnp.where(loser, W - bl, -1), axis=1, keepdims=True)
        cend = jnp.minimum(jnp.where(t >= 2, W, dm), W - 1)
        ep_drs = jnp.where(running, jnp.maximum(cend - col0 + 1, 0), 0)
        rm = jnp.where(running & (cend >= col0),
                       (1 << (W - col0)) - (1 << (W - 1 - cend)), 0)
        # mixed-read columns = divergence bits of losers in the read range
        hib = 1 << jnp.maximum(bl - 1, 0)          # loser's divergence bit
        ebits = _lane_or(jnp.where(loser, hib, 0)) & rm
        udr = udr + jax.lax.population_count(ebits)
        if k > 0:
            # a read refreshes the path digit (the winner's bit) and
            # closes any prefix hole in the read range (rm excludes the
            # resume column, so its hole survives until re-read)
            pathv = jnp.where(running,
                              (pathv & ~rm) | ((kmin ^ flipv) & rm), pathv)
            # state-record pushes at the mixed columns; at capacity k the
            # shallowest present column (the LIFO's oldest entry) drops
            # first, so the survivors are the deepest k (lowest bits) of
            # old + new
            present = jnp.where(running, _lowest_bits(present | ebits, k, W),
                                present)

        # ---- emission: whole tie set, consecutive index-order ranks ----
        r = jnp.minimum(t, jnp.maximum(stop_n - out, 0))
        p = exclusive_prefix(isw.astype(jnp.int32))
        emit_now = isw & (p < r) & running
        rank = jnp.where(emit_now, out + p, rank)
        out = out + jnp.where(running, r, 0)
        # zero reads: the set came straight off the LIFO — a lone number
        # costs its last-number-check cycle, ties drain one per repeat
        # cycle; after reads the first tie rides the LSB read cycle
        emit_cyc = jnp.where(ep_drs == 0,
                             jnp.where(t > 1, r, 1),
                             jnp.maximum(r - 1, 0))
        cyc = cyc + jnp.where(running, emit_cyc, 0) + ep_drs
        drs = drs + ep_drs
        return (pathv, skipv, present, rank, out, cyc, drs, rlc, udr)

    def body(_, carry):
        for _u in range(max(1, unroll)):
            carry = episode(carry)
        return carry

    zero = jnp.zeros((bm, 1), jnp.int32)
    init = (zero,                                  # path word
            zero,                                  # skip word
            zero,                                  # present word
            jnp.full((bm, Np), -1, jnp.int32),     # rank
            zero, zero, zero, zero, zero)
    trips = -(-stop_n // max(1, unroll))
    carry = jax.lax.fori_loop(0, trips, body, init)
    rank_ref[...] = carry[3]
    li = jax.lax.broadcasted_iota(jnp.int32, (bm, _NCNT), 1)
    cnt = jnp.zeros((bm, _NCNT), jnp.int32)
    for col, v in zip((_CYC, _DRS, _RLC, _UDR, _OUT),
                      (carry[5], carry[6], carry[7], carry[8], carry[4])):
        cnt = jnp.where(li == col, v, cnt)
    cnt_ref[...] = cnt


def _block_rows(block_rows: Optional[int], b: int) -> int:
    """Instances per grid program.  A block shorter than the batch must
    span whole 8-row sublane tiles, so it rounds up to a multiple of 8."""
    if block_rows is None or block_rows >= b:
        return b
    return min(b, -(-max(1, block_rows) // 8) * 8)


def _stop_n(n: int, stop_after: Optional[int]) -> int:
    """Emissions the kernel makes: ``stop_after`` capped at N, at least 1."""
    return max(n if stop_after is None else min(stop_after, n), 1)


def _launch(planes, sign_bits, *, k, fmt, ascending, stop_after,
            block_rows, unroll, interpret):
    """The ``pallas_call``: (rank ring (B, N), counter block (B, _NCNT));
    rank[i] is element i's emission slot, -1 if never emitted."""
    interpret = backend.use_interpret(interpret)
    assert planes.ndim == 3, "fused_tns_planes expects (B, W, N) planes"
    assert planes.shape[1] <= _SIGN_BIT, "digit keys are packed into int32"
    B, W, N = planes.shape
    stop_n = _stop_n(N, stop_after)
    Np = pad_lanes(N)
    bm = _block_rows(block_rows, B)
    b_pad = -(-B // bm) * bm
    # one int32 word per lane: the digit column (MSB = column 0) and the
    # sign plane above it
    words = pack_columns(planes)
    if sign_bits is not None:
        words = words | ((sign_bits != 0).astype(jnp.int32) << _SIGN_BIT)
    rank, cnt = pl.pallas_call(
        functools.partial(_fused_tns_kernel, width=W, n_valid=N, k=k,
                          fmt=fmt, ascending=ascending, stop_n=stop_n,
                          unroll=unroll),
        grid=(b_pad // bm,),
        in_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, Np), lambda i: (i, 0)),
                   pl.BlockSpec((bm, _NCNT), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b_pad, Np), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, _NCNT), jnp.int32)],
        interpret=interpret,
    )(pad_to(words, (b_pad, Np), 0))
    return rank[:B, :N], cnt[:B]


@functools.partial(
    jax.jit,
    static_argnames=("k", "fmt", "ascending", "stop_after", "block_rows",
                     "unroll", "interpret"))
def _fused_tns_rank(planes: jnp.ndarray,
                    sign_bits: Optional[jnp.ndarray] = None,
                    *, k: int, fmt: str = bp.UNSIGNED,
                    ascending: bool = True,
                    stop_after: Optional[int] = None,
                    block_rows: Optional[int] = None, unroll: int = 1,
                    interpret: bool | None = None):
    """Kernel launch and its epilogue, as one (B, _NOUT + S) int32 array:
    the counter lanes (cycles, DRs, reload cycles, useful DRs), then, for
    stop_n <= DEVICE_PERM_MAX, the permutation's first S = stop_n slots,
    else the S = N rank ring for the host to invert."""
    rank, cnt = _launch(planes, sign_bits, k=k, fmt=fmt,
                        ascending=ascending, stop_after=stop_after,
                        block_rows=block_rows, unroll=unroll,
                        interpret=interpret)
    B, N = rank.shape
    stop_n = _stop_n(N, stop_after)
    body = rank
    if stop_n <= DEVICE_PERM_MAX:
        # slot j holds the one lane ranked j: emitted ranks are unique and
        # slots below stop_n all filled (-1, as on the host, if not)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, N), 2)
        slot = jax.lax.broadcasted_iota(jnp.int32, (1, stop_n, 1), 1)
        body = jnp.max(jnp.where(rank[:, None, :] == slot, lane, -1),
                       axis=2)
    return jnp.concatenate([cnt[:, :_NOUT], body], axis=1)


def _rank_to_perm_np(rank: np.ndarray) -> np.ndarray:
    """Invert the rank ring on the host, for stop_n > DEVICE_PERM_MAX
    (below it the device finds the slots): XLA:CPU lowers the equivalent
    scatter to a scalar loop (~3.6ms for 64x1024), numpy fancy indexing
    does it in ~0.1ms — this is on the serving path, so it matters."""
    B, N = rank.shape
    perm = np.full((B, N), -1, dtype=np.int32)
    rows, lanes = np.nonzero(rank >= 0)
    perm[rows, rank[rows, lanes]] = lanes
    return perm


@functools.partial(
    jax.jit,
    static_argnames=("k", "fmt", "ascending", "stop_after", "block_rows",
                     "unroll", "interpret"))
def fused_tns_planes(planes: jnp.ndarray,
                     sign_bits: Optional[jnp.ndarray] = None,
                     *, k: int, fmt: str = bp.UNSIGNED,
                     ascending: bool = True,
                     stop_after: Optional[int] = None,
                     block_rows: Optional[int] = None, unroll: int = 1,
                     interpret: bool | None = None) -> FusedOut:
    """Run the fused TNS kernel on (B, W, N) bit planes (MSB first, the
    physical array image).  One grid program sorts ``block_rows``
    instances with their packed (N,) word rows resident in VMEM.  Cycle / DR /
    reload counts match :func:`repro.core.tns.tns_sort_planes` exactly;
    ``useful_drs`` additionally counts only the mixed reads.
    ``interpret=None`` resolves per backend."""
    rank, cnt = _launch(
        planes, sign_bits, k=k, fmt=fmt, ascending=ascending,
        stop_after=stop_after, block_rows=block_rows, unroll=unroll,
        interpret=interpret)
    B, N = rank.shape
    # rank -> forward permutation (same scatter as the batched machine)
    src = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    tgt = jnp.where(rank >= 0, rank, N)
    perm = jnp.full((B, N + 1), -1, dtype=jnp.int32)
    perm = perm.at[jnp.arange(B)[:, None], tgt].set(src)[:, :N]
    return FusedOut(perm, cnt[:, _CYC], cnt[:, _DRS], cnt[:, _RLC],
                    cnt[:, _UDR])


def fused_tns_sort(values, *, width: int, k: int, fmt: str = bp.UNSIGNED,
                   ascending: bool = True, level_bits: int = 1,
                   stop_after: Optional[int] = None,
                   block_rows: Optional[int] = None,
                   unroll: int = 1) -> FusedOut:
    """Encode a (B, N) batch like programming the memristor array (via the
    fault-injectable ``bitplane.read_planes`` path) and run the fused
    kernel — or, under ``REPRO_PALLAS=jnp``, the while_loop oracle."""
    if level_bits != 1:
        from repro.sort.registry import EngineUnsupported
        raise EngineUnsupported(
            "fused Pallas TNS runs binary (level_bits=1) planes; "
            "multi-level stays on the while_loop machine")
    x = np.asarray(values)
    assert x.ndim == 2, "fused_tns_sort expects a (B, N) batch"
    with spans.span("sort.encode"):
        digits = bp.to_bitplanes(x, width, fmt)
        digits = bp.read_planes(digits, kind="bit", level_bits=1)
        sign = None
        if fmt in (bp.SIGNMAG, bp.FLOAT):
            sign = bp.sign_plane(x, width, fmt)
    if sign is not None:
        sign = spans.to_device(sign)
    stop_n = _stop_n(x.shape[1], stop_after)
    if backend.use_ref(None):
        from repro.core import tns as jt
        out = jt.tns_sort_planes_batched(
            jnp.asarray(digits.astype(np.int32)), sign, k=k, fmt=fmt,
            ascending=ascending, stop_after=stop_after)
        perm, cycles, drs, rlc = map(spans.to_host, out[:4])
        # the machine has no mixed-read counter; drs upper-bounds it
        return FusedOut(perm[:, :stop_n], cycles, drs, rlc, drs)
    planes = spans.to_device(digits)
    with spans.span("sort.dispatch"):
        out = _fused_tns_rank(planes, sign, k=k, fmt=fmt,
                              ascending=ascending, stop_after=stop_after,
                              block_rows=block_rows, unroll=unroll)
    out = spans.to_host(out)
    on_device = stop_n <= DEVICE_PERM_MAX
    spans.count("device_perm", int(on_device))
    perm = out[:, _NOUT:]            # the slots, or the ring to invert
    if not on_device:
        with spans.span("sort.rank_to_perm"):
            perm = _rank_to_perm_np(perm)[:, :stop_n]
    return FusedOut(perm, out[:, _CYC], out[:, _DRS], out[:, _RLC],
                    out[:, _UDR])
