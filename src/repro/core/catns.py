"""Cross-array TNS (CA-TNS) strategies in JAX (paper §2.3).

* **Multi-bank** (§2.3.1): the dataset is sharded by *numbers* over a mesh
  axis; each bank runs the TNS controller on its local slice and the paper's
  "cross-array processor" — which ORs the not-all-0s / not-all-1s / load
  signals across banks — becomes a handful of scalar ``psum``/``pmin``
  collectives per cycle.  Cycle-for-cycle identical to basic TNS (eq. 2),
  which the tests assert.  This is also the template for how the sort engine
  distributes on a TPU pod: bank == device, cross-array processor == ICI
  all-reduce.

* **Bit-slice** (§2.3.2): functional two-phase composition (upper digits
  resolve groups, lower digits refine).  The *pipelined* cycle count is the
  event-driven oracle's job (ref_tns.bitslice_sort); here we provide the
  throughput-mode equivalent plus the paper's eq. (4) estimate.

* **Multi-level** (§2.3.3) is already native to the engine
  (``level_bits > 1`` in tns.py).

* **BTS** baseline (prior art [42]) as a jittable reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bitplane as bp
from repro.core import tns as jt


# ---------------------------------------------------------------------------
# BTS baseline (S3): every min search walks MSB->LSB; N*W cycles.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("fmt", "ascending"))
def bts_sort_planes(digits: jnp.ndarray,
                    sign_bits: Optional[jnp.ndarray] = None,
                    *, fmt: str = bp.UNSIGNED, ascending: bool = True):
    digits = digits.astype(jnp.int32)
    D, N = digits.shape

    def min_iter(carry, _):
        alive, perm, out_cnt = carry

        def col_step(col, valid):
            row = jnp.take(digits, col, axis=0)
            ones = jnp.any(valid & (row == 1))
            zeros = jnp.any(valid & (row == 0))
            mixed = ones & zeros
            if sign_bits is None:
                npend = jnp.bool_(False)
            else:
                s = sign_bits if ascending else ~sign_bits
                npend = jnp.any(alive & s)
            exc = jt._exclude_value(col, fmt, ascending, npend)
            return jnp.where(mixed, valid & (row != exc), valid)

        valid = jax.lax.fori_loop(0, D, col_step, alive)
        idx = jnp.argmax(valid).astype(jnp.int32)
        perm = perm.at[out_cnt].set(idx)
        alive = alive.at[idx].set(False)
        return (alive, perm, out_cnt + 1), None

    init = (jnp.ones(N, dtype=bool), jnp.full(N, -1, jnp.int32), jnp.int32(0))
    (alive, perm, _), _ = jax.lax.scan(min_iter, init, None, length=N)
    cycles = jnp.int32(N * D)
    return jt.TnsOut(perm, cycles, cycles, jnp.int32(0))


def bts_sort(values, width: int, fmt: str = bp.UNSIGNED, ascending: bool = True):
    x = np.asarray(values)
    digits = bp.read_planes(bp.to_bitplanes(x, width, fmt))
    sign = None
    if fmt in (bp.SIGNMAG, bp.FLOAT):
        u = bp.raw_bits(x, width, fmt).astype(np.uint64)
        sign = jnp.asarray(((u >> np.uint64(width - 1)) & 1).astype(bool))
    return bts_sort_planes(jnp.asarray(digits.astype(np.int32)), sign,
                           fmt=fmt, ascending=ascending)


# ---------------------------------------------------------------------------
# Multi-bank CA-TNS under shard_map.
# ---------------------------------------------------------------------------


class MbCarry(NamedTuple):
    alive: jnp.ndarray          # (Nl,) local
    valid: jnp.ndarray          # (Nl,) local
    col: jnp.ndarray            # replicated scalar state (identical per bank)
    lifo_mask: jnp.ndarray      # (k, Nl) local slices of recorded status
    lifo_digit: jnp.ndarray     # (k,)
    lifo_len: jnp.ndarray
    reload_pending: jnp.ndarray
    rank: jnp.ndarray           # (Nl,) emission rank, -1 if not emitted
    out_cnt: jnp.ndarray
    cycles: jnp.ndarray
    drs: jnp.ndarray
    reload_cycles: jnp.ndarray


def _mb_body(digits_l, sign_l, fmt, ascending, level_bits, axis):
    """One synchronized controller cycle for the local bank; all control
    decisions use cross-bank collectives (the cross-array processor)."""
    D, Nl = digits_l.shape
    BIG = jnp.int32(1 << 30)

    def gsum(x):
        return jax.lax.psum(x, axis)

    def gany(m):
        return gsum(jnp.sum(m.astype(jnp.int32))) > 0

    def offset():
        return jax.lax.axis_index(axis).astype(jnp.int32) * Nl

    def neg_pending(alive):
        if sign_l is None:
            return jnp.bool_(False)
        s = sign_l if ascending else ~sign_l
        return gany(alive & s)

    def emit_global_first(st: MbCarry, mask):
        """Emit the globally-lowest-index member of ``mask`` (synchronized
        across banks, S8.1 cycle 4)."""
        local_first = jnp.where(jnp.any(mask), jnp.argmax(mask).astype(jnp.int32),
                                BIG - offset())
        gidx = jax.lax.pmin(local_first + offset(), axis)
        local = gidx - offset()
        is_mine = (local >= 0) & (local < Nl)
        clear = jnp.zeros(Nl, bool).at[jnp.clip(local, 0, Nl - 1)].set(is_mine)
        rank = jnp.where(clear, st.out_cnt, st.rank)
        return st._replace(alive=st.alive & ~clear, valid=st.valid & ~clear,
                           rank=rank, out_cnt=st.out_cnt + 1)

    def push(st: MbCarry, digit, status):
        k = st.lifo_mask.shape[0]
        if k == 0:
            return st
        full = st.lifo_len >= k
        lm = jnp.where(full,
                       jnp.concatenate([st.lifo_mask[1:], st.lifo_mask[-1:]], 0),
                       st.lifo_mask)
        ld = jnp.where(full,
                       jnp.concatenate([st.lifo_digit[1:], st.lifo_digit[-1:]], 0),
                       st.lifo_digit)
        pos = jnp.where(full, k - 1, st.lifo_len)
        return st._replace(lifo_mask=lm.at[pos].set(status),
                           lifo_digit=ld.at[pos].set(digit),
                           lifo_len=jnp.minimum(st.lifo_len + 1, k))

    def do_reload(st: MbCarry):
        k = st.lifo_mask.shape[0]
        st = st._replace(reload_pending=jnp.bool_(False))
        if k == 0:
            return st._replace(valid=st.alive, col=jnp.int32(0)), jnp.bool_(False)
        has0 = st.lifo_len > 0
        t0 = jnp.maximum(st.lifo_len - 1, 0)
        live0 = st.lifo_mask[t0] & st.alive
        drained0 = has0 & ~gany(live0)          # load-check is synchronized
        len1 = jnp.where(drained0, st.lifo_len - 1, st.lifo_len)
        has1 = len1 > 0
        t1 = jnp.maximum(len1 - 1, 0)
        live1 = st.lifo_mask[t1] & st.alive
        drained1 = has1 & ~gany(live1)
        spent = drained0 & drained1
        valid = jnp.where(has1, live1, st.alive)
        col = jnp.where(has1, st.lifo_digit[t1], jnp.int32(0))
        st_ok = st._replace(lifo_len=len1, valid=valid, col=col)
        st_sp = st._replace(lifo_len=len1, reload_pending=jnp.bool_(True),
                            reload_cycles=st.reload_cycles + 1)
        return jax.tree.map(lambda a, b: jnp.where(spent, b, a), st_ok, st_sp), spent

    def phase2_emit(st: MbCarry):
        st2 = emit_global_first(st, st.valid)
        return st2._replace(reload_pending=gany(st2.alive))

    def phase3_repeat(st: MbCarry):
        st2 = emit_global_first(st, st.valid)
        drained = ~gany(st2.valid)
        return st2._replace(reload_pending=drained & gany(st2.alive))

    def phase45_dr(st: MbCarry):
        row = jnp.take(digits_l, st.col, axis=0).astype(jnp.int32)
        st = st._replace(drs=st.drs + 1)
        if level_bits == 1:
            ones = gany(st.valid & (row == 1))
            zeros = gany(st.valid & (row == 0))
            mixed = ones & zeros
            exc = jt._exclude_value(st.col, fmt, ascending, neg_pending(st.alive))
            keep = st.valid & (row != exc)
            rec = st.col + 1
        else:
            dmin = jax.lax.pmin(jnp.min(jnp.where(st.valid, row, BIG)), axis)
            dmax = jax.lax.pmax(jnp.max(jnp.where(st.valid, row, -BIG)), axis)
            mixed = dmin != dmax
            sel = dmin if ascending else dmax
            keep = st.valid & (row == sel)
            rec = st.col
        st_pushed = push(st, rec, st.valid)
        st = jax.tree.map(lambda a, b: jnp.where(mixed, a, b), st_pushed, st)
        st = st._replace(valid=jnp.where(mixed, keep, st.valid))
        nv = gsum(jnp.sum(st.valid.astype(jnp.int32)))
        at_lsb = st.col == D - 1

        def lsb_dup(s):
            s2 = phase3_repeat(s)
            return s2._replace(col=jnp.int32(D))

        return jax.lax.cond(
            nv == 1, phase2_emit,
            lambda s: jax.lax.cond(at_lsb, lsb_dup,
                                   lambda q: q._replace(col=q.col + 1), s),
            st)

    def step(st: MbCarry):
        st = st._replace(cycles=st.cycles + 1)
        st1, spent = jax.lax.cond(st.reload_pending, do_reload,
                                  lambda s: (s, jnp.bool_(False)), st)

        def rest(s):
            nv = gsum(jnp.sum(s.valid.astype(jnp.int32)))
            return jax.lax.cond(
                nv == 1, phase2_emit,
                lambda q: jax.lax.cond(q.col >= D, phase3_repeat, phase45_dr, q),
                s)

        return jax.lax.cond(spent, lambda s: s, rest, st1)

    return step


def multibank_sort_planes(digits: jnp.ndarray,
                          sign_bits: Optional[jnp.ndarray] = None,
                          *, mesh: Mesh, axis: str = "bank", k: int,
                          fmt: str = bp.UNSIGNED, ascending: bool = True,
                          level_bits: int = 1):
    """Synchronized multi-bank TNS over ``mesh[axis]`` banks.

    ``digits`` is the full (D, N) matrix; N must divide evenly by the number
    of banks (pad datasets with +inf sentinels upstream otherwise).  Returns
    (rank, cycles, drs, reload_cycles) where ``rank[i]`` is the emission
    position of element i (i.e. the inverse permutation).
    """
    D, N = digits.shape
    banks = mesh.shape[axis]
    assert N % banks == 0, "pad N to a multiple of the bank count"
    digits = digits.astype(jnp.int32)
    have_sign = sign_bits is not None
    if not have_sign:
        sign_bits = jnp.zeros(N, dtype=bool)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=(P(axis), P(), P(), P()),
    )
    def run(digits_l, sign_l):
        Nl = digits_l.shape[1]
        kk = max(k, 1)
        step = _mb_body(digits_l, sign_l if have_sign else None,
                        fmt, ascending, level_bits, axis)
        vary = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        init = MbCarry(
            alive=vary(jnp.ones(Nl, bool)), valid=vary(jnp.ones(Nl, bool)),
            col=jnp.int32(0),
            lifo_mask=vary(jnp.zeros((kk if k > 0 else 0, Nl), bool)),
            lifo_digit=jnp.zeros(kk if k > 0 else 0, jnp.int32),
            lifo_len=jnp.int32(0), reload_pending=jnp.bool_(False),
            rank=vary(jnp.full(Nl, -1, jnp.int32)), out_cnt=jnp.int32(0),
            cycles=jnp.int32(0), drs=jnp.int32(0), reload_cycles=jnp.int32(0))
        limit = jnp.int32(4 * N * D + 64)

        def cond(st: MbCarry):
            return (st.out_cnt < N) & (st.cycles < limit)

        fin = jax.lax.while_loop(cond, step, init)
        return fin.rank, fin.cycles, fin.drs, fin.reload_cycles

    rank, cycles, drs, rl = run(digits, sign_bits)
    return rank, cycles, drs, rl


def multibank_sort(values, width: int, k: int, *, mesh: Mesh,
                   axis: str = "bank", fmt: str = bp.UNSIGNED,
                   ascending: bool = True, level_bits: int = 1):
    x = np.asarray(values)
    if level_bits == 1:
        digits = bp.to_bitplanes(x, width, fmt)
    else:
        digits = bp.to_digitplanes(x, width, fmt, level_bits)
    digits = bp.read_planes(digits, kind="bit" if level_bits == 1 else
                            "digit", level_bits=level_bits,
                            banks=mesh.shape[axis])
    sign = None
    if fmt in (bp.SIGNMAG, bp.FLOAT):
        u = bp.raw_bits(x, width, fmt).astype(np.uint64)
        sign = jnp.asarray(((u >> np.uint64(width - 1)) & 1).astype(bool))
    rank, cycles, drs, rl = multibank_sort_planes(
        jnp.asarray(digits.astype(np.int32)), sign, mesh=mesh, axis=axis,
        k=k, fmt=fmt, ascending=ascending, level_bits=level_bits)
    rank = np.asarray(rank)
    perm = np.empty_like(rank)
    perm[rank] = np.arange(len(rank))
    return jt.TnsOut(jnp.asarray(perm), cycles, drs, rl)


# ---------------------------------------------------------------------------
# Bit-slice: throughput-mode composition + eq. (4) latency estimate.
# ---------------------------------------------------------------------------


def bitslice_estimate_cycles(values, width: int, k: int, slice_widths,
                             fmt: str = bp.UNSIGNED) -> dict:
    """Paper eq. (4): T_bs ~= max_i T_TNS(N, W_i) — estimated from per-slice
    TNS runs on the *same* dataset truncated to each slice; the exact
    pipelined count comes from ref_tns.bitslice_sort."""
    x = np.asarray(values)
    u = bp.raw_bits(x, width, fmt).astype(np.uint64)
    offs = np.cumsum([0] + list(slice_widths))
    per_slice = []
    for i, w in enumerate(slice_widths):
        shift = np.uint64(width - offs[i + 1])
        part = ((u >> shift) & np.uint64((1 << w) - 1)).astype(np.uint32)
        out = jt.tns_sort(part, width=w, k=k)
        per_slice.append(int(out.cycles))
    return {"per_slice": per_slice, "estimate": max(per_slice)}
