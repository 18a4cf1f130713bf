"""Built-in sort engines — importing this module registers them.

Latency-mode engines are the cycle-faithful controllers (paper §2.2-2.3);
throughput-mode engines are the TPU-native vectorized forms of the same
digit-read machinery.  All engines produce the SAME permutation for the
same input (ties resolved by lowest index first, the hardware's emission
order) — asserted by the registry-parity suite in
tests/test_sort_engine.py — so callers pick purely by budget: cycles/DR
observables (latency) vs wall-clock (throughput).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import bitplane as bp
from repro.core import catns
from repro.core import radix_select as rs
from repro.core import ref_tns as rt
from repro.core import tns as jt
from repro.runtime import spans
from repro.sort.registry import EngineUnsupported, register
from repro.sort.result import SortResult


def _finish(x, perm, *, engine, fmt, width, k=0, level_bits=1,
            stop_after=None, cycles=None, drs=None, reload_cycles=None,
            strategy=None) -> SortResult:
    perm, cycles, drs, reload_cycles = map(
        spans.to_host, (perm, cycles, drs, reload_cycles))
    with spans.span("sort.finish"):
        if stop_after is not None:
            perm = perm[..., :stop_after]
        vals = np.take_along_axis(np.asarray(x), perm, axis=-1)
        return SortResult(values=vals, indices=perm, engine=engine, fmt=fmt,
                          width=width, n=x.shape[-1], cycles=cycles, drs=drs,
                          reload_cycles=reload_cycles, strategy=strategy,
                          k=k, level_bits=level_bits)


# ---------------------------------------------------------------------------
# Latency mode (cycle-faithful controllers)
# ---------------------------------------------------------------------------


@register("tns", mode="latency", strategy="tns", supports_stop_after=True,
          supports_batch=True,
          description="Cycle-faithful TNS (JAX while_loop machine; batched "
                      "bit-parallel fast path for (B, N) inputs)")
def _tns(x, *, width, fmt, k, ascending, level_bits, stop_after,
         ideal_lifo=False):
    call = dict(width=width, k=k, fmt=fmt, ascending=ascending,
                level_bits=level_bits, ideal_lifo=ideal_lifo,
                stop_after=stop_after)
    if x.ndim == 2 and x.shape[-1] < (1 << 15):
        out = jt.tns_sort_batch(x, **call)
    elif x.ndim == 2:
        # the batched machine's packed-count trick caps N per bank at
        # 2^15; larger banks fall back to a per-instance loop
        outs = [jt.tns_sort(x[b], **call) for b in range(x.shape[0])]
        out = jt.TnsOut(*(np.stack([np.asarray(getattr(o, f)) for o in outs])
                          for f in jt.TnsOut._fields))
    else:
        out = jt.tns_sort(x, **call)
    return _finish(x, out.perm, engine="tns", fmt=fmt, width=width, k=k,
                   level_bits=level_bits, stop_after=stop_after,
                   cycles=out.cycles, drs=out.drs,
                   reload_cycles=out.reload_cycles, strategy="tns")


@register("ml", mode="latency", strategy="ml", supports_stop_after=True,
          supports_batch=True,
          description="Multi-level TNS (§2.3.3): radix-2^n cells, fewer "
                      "digit reads per number")
def _ml(x, *, width, fmt, k, ascending, level_bits, stop_after, **kw):
    lb = level_bits if level_bits > 1 else 4
    # a radix-2^n digit straddles the sign/exponent bits, so signed and
    # float formats are first linearized to order-preserving unsigned
    # keys (the classic radix transform — S6's exclusion polarity folded
    # into the encoding); cycle counts are identical to sorting the raw
    # planes since the key transform is a per-cell remap
    keys = bp.sort_key(x, width, fmt)
    res = _tns(keys, width=width, fmt=bp.UNSIGNED, k=k, ascending=ascending,
               level_bits=lb, stop_after=stop_after)
    res.values = np.take_along_axis(np.asarray(x), res.indices, axis=-1)
    res.engine, res.strategy, res.fmt = "ml", "ml", fmt
    return res


@register("mb", mode="latency", strategy="mb", supports_stop_after=True,
          supports_batch=True,
          description="Multi-bank CA-TNS (§2.3.1): cycle-identical to TNS "
                      "(eq. 2, asserted vs shard_map in tests) at the "
                      "multi-bank operating point; banks shard N")
def _mb(x, *, width, fmt, k, ascending, level_bits, stop_after, banks=2,
        **kw):
    res = _tns(x, width=width, fmt=fmt, k=k, ascending=ascending,
               level_bits=level_bits, stop_after=stop_after)
    res.engine, res.strategy, res.banks = "mb", "mb", banks
    return res


@register("tns-oracle", mode="latency", strategy="tns",
          supports_stop_after=True,
          description="Python event-driven oracle (ground truth the JAX "
                      "machines are cycle-checked against)")
def _tns_oracle(x, *, width, fmt, k, ascending, level_bits, stop_after,
                ideal_lifo=False):
    out = rt.tns_sort(x, width=width, k=k, fmt=fmt, ascending=ascending,
                      level_bits=level_bits, ideal_lifo=ideal_lifo,
                      stop_after=stop_after)
    return _finish(x, out.perm, engine="tns-oracle", fmt=fmt, width=width,
                   k=k, level_bits=level_bits,
                   cycles=out.cycles, drs=out.drs,
                   reload_cycles=out.reload_cycles, strategy="tns")


@register("bts", mode="latency", strategy="bts",
          supports_stop_after=True,
          description="Bit-traversal sort baseline (prior art [42]): every "
                      "min search restarts at the MSB; N*W cycles")
def _bts(x, *, width, fmt, k, ascending, level_bits, stop_after, **kw):
    out = catns.bts_sort(x, width=width, fmt=fmt, ascending=ascending)
    m = x.shape[-1] if stop_after is None else min(stop_after, x.shape[-1])
    # BTS latency is exactly W cycles per emitted number, so stopping
    # after m numbers is m*W cycles — no emulation slack
    d = width  # one DR per cycle
    return _finish(x, out.perm, engine="bts", fmt=fmt, width=width,
                   stop_after=stop_after, cycles=m * d, drs=m * d,
                   reload_cycles=0, strategy="bts")


@register("bitslice", mode="latency", strategy="bs",
          formats=(bp.UNSIGNED,),
          description="Bit-slice CA-TNS (§2.3.2): pipelined upper/lower "
                      "slice arrays (event-driven oracle; unsigned "
                      "ascending)")
def _bitslice(x, *, width, fmt, k, ascending, level_bits, stop_after,
              slice_widths=None, **kw):
    if not ascending:
        raise EngineUnsupported("bitslice oracle models ascending sorts")
    if slice_widths is None:
        slice_widths = [width // 2, width - width // 2]
    out = rt.bitslice_sort(x, width=width, k=max(k, 1),
                           slice_widths=list(slice_widths))
    # stop_after truncates the emission (cycles stay full-pipeline: the
    # slices drain concurrently, so early-stop savings are sub-linear)
    return _finish(x, out.perm, engine="bitslice", fmt=fmt, width=width,
                   k=k, stop_after=stop_after, cycles=out.cycles,
                   drs=out.drs, reload_cycles=out.reload_cycles,
                   strategy="bs")


# ---------------------------------------------------------------------------
# Throughput mode (vectorized digit-read machinery)
# ---------------------------------------------------------------------------


def _unsigned_keys(x, width, fmt, ascending) -> np.ndarray:
    with spans.span("sort.encode"):
        keys = bp.sort_key(x, width, fmt)
        if not ascending:
            dt = keys.dtype
            keys = (((~keys.astype(np.uint64)) & np.uint64((1 << width) - 1))
                    .astype(dt))
        return keys


@register("radix", mode="throughput", supports_stop_after=True,
          supports_batch=True,
          description="LSB-first counting radix sort over order-preserving "
                      "keys (stable, comparison-free, vmappable)")
def _radix(x, *, width, fmt, k, ascending, level_bits, stop_after,
           r=None, **kw):
    keys = _unsigned_keys(x, width, fmt, ascending)
    rr = r or (8 if width % 8 == 0 else 4)
    keys = spans.to_device(keys)
    spans.count("radix_dense", int(rs.takes_dense_path(keys.shape[-1])))
    with spans.span("sort.dispatch"):
        perm = rs.radix_sort_keys(keys, r=rr)
    return _finish(x, perm, engine="radix", fmt=fmt, width=width,
                   stop_after=stop_after)


@register("pallas-topk", mode="throughput", supports_stop_after=True,
          supports_batch=True,
          description="Fused Pallas min-search kernel: k smallest emitted "
                      "in order (interpret on CPU, compiled on TPU)")
def _pallas_topk(x, *, width, fmt, k, ascending, level_bits, stop_after,
                 **kw):
    keys = _unsigned_keys(x, width, fmt, ascending).astype(np.uint32)
    m = x.shape[-1] if stop_after is None else min(stop_after, x.shape[-1])
    if m > 32:
        # the kernel unrolls m min-searches in registers — a top-m engine,
        # not a full sorter (the router hot path is m <= 8)
        raise EngineUnsupported(
            f"pallas-topk extracts at most 32 minima per call (asked {m}); "
            "use stop_after, or the 'radix' engine for full sorts")
    kb = jnp.asarray(keys)
    squeeze = kb.ndim == 1
    if squeeze:
        kb = kb[None]
    _, idx = _topk_keys_dispatch(kb, m)
    if squeeze:
        idx = idx[0]
    return _finish(x, idx, engine="pallas-topk", fmt=fmt, width=width)


def _topk_keys_dispatch(keys: jnp.ndarray, m: int):
    """m-smallest keys via the fused Pallas kernel (keys already encode
    direction), honoring the backend's pure-jnp fallback."""
    from repro.kernels import backend, radix_topk, ref
    if backend.use_ref(None):
        return ref.topk_keys_ref(keys, m)
    return radix_topk.topk_keys(keys, m)


@register("pallas-tns", mode="throughput", strategy="tns",
          supports_stop_after=True, supports_batch=True,
          description="Fused Pallas TNS pipeline: digit read + tree-node "
                      "skipping + winner write-back in one kernel; "
                      "cycle/DR parity with the while_loop machine "
                      "(interpret on CPU, compiled on TPU)")
def _pallas_tns(x, *, width, fmt, k, ascending, level_bits, stop_after,
                block_rows=None, unroll=None, **kw):
    if level_bits != 1:
        raise EngineUnsupported(
            "pallas-tns runs binary (level_bits=1) planes; multi-level "
            "stays on the 'ml' while_loop machine")
    from repro.kernels import autotune, fused_tns
    xb = np.asarray(x)
    squeeze = xb.ndim == 1
    if squeeze:
        xb = xb[None]
    b, n = xb.shape
    if n >= (1 << 15):
        raise EngineUnsupported(
            "pallas-tns supports N < 32768 per bank (same packed-count "
            "bound as the batched machine its oracle path reuses)")
    if width > 30:
        raise EngineUnsupported(
            "pallas-tns packs a lane's digit column into one int32 key; "
            "width <= 30 required (32-bit data stays on the while_loop "
            "machines)")
    m = n if stop_after is None else min(stop_after, n)
    if block_rows is None and unroll is None:
        # the committed autotune table picks the grid shape per cell
        with spans.span("sort.params"):
            params = autotune.best_params(fmt, n, m, b)
        block_rows = params["block_rows"] or None
        unroll = params["unroll"]
    out = fused_tns.fused_tns_sort(
        xb, width=width, k=k, fmt=fmt, ascending=ascending,
        stop_after=stop_after, block_rows=block_rows, unroll=unroll or 1)
    perm, cycles, drs, rlc = out[:4]
    if squeeze:
        perm, cycles, drs, rlc = perm[0], cycles[0], drs[0], rlc[0]
    return _finish(x, perm, engine="pallas-tns", fmt=fmt, width=width,
                   k=k, stop_after=stop_after, cycles=cycles, drs=drs,
                   reload_cycles=rlc, strategy="tns")
