"""Unified sort-engine subsystem tests.

* Registry parity: EVERY registered engine produces the identical
  permutation for the same input across data formats, directions and
  stop_after/k — ties always resolve to the lowest index first (the
  hardware's emission order: phase-3 repeat mode drains the tie set in
  array order, and the throughput engines are stable sorts).
* Batched TNS: the (B, N) machine is cycle-for-cycle identical to a
  per-instance loop (which itself is cycle-checked against the Python
  oracle in test_tns_jax.py).
* The facade: dtype auto-encoding, registration of new engines, and the
  jittable in-model dispatchers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import sort as S
from repro.core import bitplane as bp
from repro.core import tns as jt

RNG = np.random.default_rng(7)

FMT_DATA = {
    bp.UNSIGNED: (lambda n: RNG.integers(0, 256, n).astype(np.uint8), 8),
    bp.TWOS: (lambda n: RNG.integers(-128, 128, n).astype(np.int8), 8),
    bp.SIGNMAG: (lambda n: RNG.integers(-2**14, 2**14, n), 16),
    bp.FLOAT: (lambda n: RNG.standard_normal(n).astype(np.float16), 16),
}


def _all_engine_perms(x, fmt, width, *, ascending=True, stop_after=None,
                      k=2):
    perms = {}
    for name, spec in S.engines().items():
        if fmt not in spec.formats:
            continue
        try:
            res = S.sort(x, engine=name, fmt=fmt, width=width, k=k,
                         ascending=ascending, stop_after=stop_after)
        except S.EngineUnsupported:
            continue
        perms[name] = np.asarray(res.indices)
    return perms


class TestRegistryParity:
    @pytest.mark.parametrize("fmt", list(FMT_DATA))
    def test_every_engine_same_permutation(self, fmt):
        gen, width = FMT_DATA[fmt]
        x = gen(20)
        perms = _all_engine_perms(x, fmt, width)
        assert "tns" in perms and "radix" in perms
        ref = perms["tns"]
        # ground truth: stable argsort == lowest-index-first tie order
        expect = np.argsort(np.asarray(x, np.float64) if fmt == bp.FLOAT
                            else x, kind="stable")
        np.testing.assert_array_equal(ref, expect)
        for name, perm in perms.items():
            np.testing.assert_array_equal(perm, ref, err_msg=name)

    @pytest.mark.parametrize("fmt", [bp.UNSIGNED, bp.FLOAT])
    def test_descending(self, fmt):
        gen, width = FMT_DATA[fmt]
        x = gen(18)
        perms = _all_engine_perms(x, fmt, width, ascending=False)
        ref = perms["tns"]
        keys = bp.sort_key(x, width, fmt)
        expect = np.argsort((~keys.astype(np.uint64))
                            & np.uint64((1 << width) - 1), kind="stable")
        np.testing.assert_array_equal(ref, expect)
        for name, perm in perms.items():
            np.testing.assert_array_equal(perm, ref, err_msg=name)

    @pytest.mark.parametrize("stop_after,k", [(1, 2), (5, 0), (7, 4)])
    def test_stop_after_and_k(self, stop_after, k):
        x = FMT_DATA[bp.UNSIGNED][0](24)
        perms = _all_engine_perms(x, bp.UNSIGNED, 8, stop_after=stop_after,
                                  k=k)
        assert "pallas-topk" in perms     # top-m engines join via stop_after
        ref = perms["tns"]
        assert ref.shape[-1] == stop_after
        for name, perm in perms.items():
            np.testing.assert_array_equal(perm, ref, err_msg=name)

    def test_ties_resolve_lowest_index_first(self):
        x = np.array([3, 1, 3, 1, 1, 3], dtype=np.uint8)
        perms = _all_engine_perms(x, bp.UNSIGNED, 8)
        for name, perm in perms.items():
            np.testing.assert_array_equal(perm, [1, 3, 4, 0, 2, 5],
                                          err_msg=name)

    def test_values_are_gathered(self):
        x = FMT_DATA[bp.FLOAT][0](16)
        res = S.sort(x, engine="radix")
        np.testing.assert_array_equal(np.sort(x), res.values)


class TestBatchedTns:
    @pytest.mark.parametrize("fmt,level_bits,k", [
        (bp.UNSIGNED, 1, 2), (bp.UNSIGNED, 1, 0), (bp.UNSIGNED, 2, 2),
        (bp.TWOS, 1, 2), (bp.SIGNMAG, 1, 2), (bp.FLOAT, 1, 3),
    ])
    def test_batched_equals_per_instance(self, fmt, level_bits, k):
        gen, width = FMT_DATA[fmt]
        B, N = 5, 18
        data = np.stack([gen(N) for _ in range(B)])
        out = jt.tns_sort_batch(data, width=width, k=k,
                                fmt=fmt, level_bits=level_bits)
        for b in range(B):
            o = jt.tns_sort(data[b], width=width, k=k, fmt=fmt,
                            level_bits=level_bits)
            assert int(o.cycles) == int(out.cycles[b])
            assert int(o.drs) == int(out.drs[b])
            assert int(o.reload_cycles) == int(out.reload_cycles[b])
            np.testing.assert_array_equal(np.asarray(o.perm),
                                          np.asarray(out.perm[b]))

    def test_batched_stop_after_freezes_instances(self):
        data = np.stack([FMT_DATA[bp.UNSIGNED][0](16) for _ in range(4)])
        out = jt.tns_sort_batch(data, width=8, k=2, stop_after=3)
        for b in range(4):
            o = jt.tns_sort(data[b], width=8, k=2, stop_after=3)
            assert int(o.cycles) == int(out.cycles[b])
            np.testing.assert_array_equal(np.asarray(o.perm)[:3],
                                          np.asarray(out.perm[b])[:3])

    def test_facade_batched_matches_loop(self):
        data = np.stack([FMT_DATA[bp.FLOAT][0](20) for _ in range(4)])
        res_b = S.sort(data, engine="tns", k=2)
        for b in range(4):
            res_1 = S.sort(data[b], engine="tns", k=2)
            np.testing.assert_array_equal(res_b.indices[b], res_1.indices)
            assert int(res_b.cycles[b]) == int(np.asarray(res_1.cycles))

    def test_batched_engine_without_batch_support_loops(self):
        data = np.stack([FMT_DATA[bp.UNSIGNED][0](12) for _ in range(3)])
        res = S.sort(data, engine="tns-oracle", k=2)
        ref = S.sort(data, engine="tns", k=2)
        np.testing.assert_array_equal(res.indices, ref.indices)
        np.testing.assert_array_equal(res.cycles, ref.cycles)


class TestFacade:
    def test_dtype_auto_encode(self):
        # float16 -> FLOAT/16, int64 small values -> TWOS/8, uint8 -> 8
        r = S.sort(np.array([1.5, -2.0], np.float16), engine="radix")
        assert (r.fmt, r.width) == (bp.FLOAT, 16)
        r = S.sort(np.array([-3, 100]), engine="radix")
        assert (r.fmt, r.width) == (bp.TWOS, 8)
        r = S.sort(np.array([3, 250], np.uint8), engine="radix")
        assert (r.fmt, r.width) == (bp.UNSIGNED, 8)

    def test_metrics_only_for_latency_engines(self):
        x = FMT_DATA[bp.UNSIGNED][0](16)
        assert S.sort(x, engine="tns", k=2).metrics() is not None
        assert S.sort(x, engine="radix").metrics() is None

    def test_unknown_engine_raises(self):
        with pytest.raises(KeyError):
            S.sort(np.arange(4), engine="nope")

    def test_new_engine_registration_one_file(self):
        # the tentpole promise: a new engine is one @register away
        from repro.sort.builtin_engines import _finish

        @S.register("np-sort", mode="throughput",
                    description="numpy baseline (test-only)")
        def _np_sort(x, *, width, fmt, k, ascending, level_bits,
                     stop_after, **kw):
            key = bp.sort_key(x, width, fmt)
            if not ascending:
                key = (~key.astype(np.uint64)) & np.uint64((1 << width) - 1)
            perm = np.argsort(key, kind="stable")
            return _finish(x, perm, engine="np-sort", fmt=fmt, width=width,
                           stop_after=stop_after)

        try:
            x = FMT_DATA[bp.TWOS][0](15)
            a = S.sort(x, engine="np-sort", fmt=bp.TWOS, width=8)
            b = S.sort(x, engine="tns", fmt=bp.TWOS, width=8, k=2)
            np.testing.assert_array_equal(a.indices, b.indices)
        finally:
            from repro.sort import registry
            registry._REGISTRY.pop("np-sort", None)


# (fmt, width, data): integer inputs wider than the container, negative
# keys read as unsigned, 1-D and (B, N) shapes, every container width
_ENC = np.random.default_rng(12)
ENCODINGS = {
    "u8": (bp.UNSIGNED, 8, _ENC.integers(0, 256, (3, 40)).astype(np.uint8)),
    "u8_from_int64": (bp.UNSIGNED, 8, _ENC.integers(-600, 600, 50)),
    "u12_from_uint16": (bp.UNSIGNED, 12,
                        _ENC.integers(0, 2**16, (2, 33)).astype(np.uint16)),
    "u64": (bp.UNSIGNED, 64, _ENC.integers(0, 2**63, 20, dtype=np.uint64)
            * np.uint64(2) + np.uint64(1)),
    "twos8": (bp.TWOS, 8, _ENC.integers(-128, 128, (2, 30)).astype(np.int8)),
    "twos5_from_int64": (bp.TWOS, 5, _ENC.integers(-40, 40, 30)),
    "signmag16": (bp.SIGNMAG, 16, _ENC.integers(-2**14, 2**14, 30)),
    "float16": (bp.FLOAT, 16, _ENC.standard_normal(30).astype(np.float16)),
    "float32": (bp.FLOAT, 32,
                _ENC.standard_normal((2, 20)).astype(np.float32)),
}


class TestEncoding:
    @pytest.mark.parametrize("case", list(ENCODINGS))
    def test_bitplanes_are_the_64_bit_pattern_msb_first(self, case):
        fmt, width, x = ENCODINGS[case]
        if fmt in (bp.UNSIGNED, bp.TWOS):
            u = x.astype(np.int64).astype(np.uint64)
            u &= np.uint64((1 << width) - 1) if width < 64 else ~np.uint64(0)
            raw = bp.raw_bits(x, width, fmt)
            assert raw.dtype == bp._container(width)
            np.testing.assert_array_equal(raw.astype(np.uint64), u)
        else:
            u = bp.raw_bits(x, width, fmt).astype(np.uint64)
        shifts = np.arange(width - 1, -1, -1, dtype=np.uint64)
        want = ((u[..., None, :] >> shifts[:, None]) & np.uint64(1))
        got = bp.to_bitplanes(x, width, fmt)
        assert got.dtype == np.uint8 and got.shape == want.shape
        np.testing.assert_array_equal(got, want.astype(np.uint8))


class TestInModelDispatchers:
    def test_topk_engines_agree_with_lax(self):
        x = jnp.asarray(RNG.standard_normal((3, 5, 24)), jnp.float32)
        vl, _ = jax.lax.top_k(x, 4)
        for name in S.TOPK_ENGINES:
            v, i = S.topk(x, 4, engine=name)
            np.testing.assert_allclose(np.asarray(v), np.asarray(vl),
                                       err_msg=name)

    def test_topk_mask_and_prune_mask(self):
        x = jnp.asarray(RNG.standard_normal(64), jnp.float32)
        m = np.asarray(S.topk_mask(x, 8, largest=True))
        assert m.sum() == 8
        assert set(np.flatnonzero(m)) == set(
            np.asarray(x).argsort()[-8:])
        pm = np.asarray(S.prune_mask(x, 8))
        assert pm.sum() == 8
        assert set(np.flatnonzero(pm)) == set(
            np.abs(np.asarray(x)).argsort()[:8])
