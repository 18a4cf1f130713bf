"""Resilience subsystem: fault injection (repro.runtime.faults), the
verify-and-repair wrapper (repro.sort.resilient), the fault-tolerant
multi-bank engine, and the device-model calibration it is anchored to."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro import sort as sort_engine
from repro.core import bitplane as bp
from repro.core import device_model as dm
from repro.runtime import faults
from repro.sort import resilient


def _data(n=64, seed=0, width=16):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << width, n).astype(
        np.uint16 if width <= 16 else np.uint32)


# ---------------------------------------------------------------------------
# Device-model calibration regression (ISSUE satellite).
# ---------------------------------------------------------------------------


class TestDeviceModelCalibration:
    def test_write_verify_matches_paper(self):
        rng = np.random.default_rng(0)
        st = dm.write_verify(rng.integers(0, 8, 200_000), seed=1)
        # §5.2: average 13.95 pulses, PFR 1.224% — the model is a
        # numerical fit, hold it to the calibrated neighborhood
        assert abs(st.mean_pulses - 13.95) < 0.5
        assert abs(st.pfr - 0.01224) < 0.0035

    def test_level_error_rate_monotone_in_level_bits(self):
        errs = [dm.level_error_rate(lb) for lb in (1, 2, 3)]
        assert errs == sorted(errs)
        assert errs[-1] > 0  # 8-state overlap is nonzero

    def test_operating_ber_cached(self):
        dm.operating_ber.cache_clear()
        t0 = time.perf_counter()
        a = dm.operating_ber(3)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        b = dm.operating_ber(3)
        warm = time.perf_counter() - t0
        assert a == b
        assert dm.operating_ber.cache_info().hits >= 1
        assert warm < cold
        assert 0.0 < a < 0.05  # calibrated ML-3bit operating point

    def test_sorting_accuracy_nan_safe(self):
        x = np.array([3.0, np.nan, 1.0, 2.0])
        perm = np.argsort(x)  # numpy sorts NaN last
        assert dm.sorting_accuracy(x, perm) == 1.0
        bad = np.array([1, 0, 2, 3])  # NaN emitted first
        assert dm.sorting_accuracy(x, bad) < 1.0


# ---------------------------------------------------------------------------
# Fault-injection harness.
# ---------------------------------------------------------------------------


class TestFaultSpec:
    def test_parse_spec_roundtrip(self):
        spec = faults.parse_spec(
            "ber=0.01,banks=4,dead_banks=1:2,seed=7,parity_ecc=on,"
            "redundant_reads=3,stuck_zero=0.02,delay_s=0.5,delay_prob=0.1")
        assert spec.ber == 0.01 and spec.banks == 4 and spec.seed == 7
        assert spec.dead_banks == (1, 2) and spec.parity_ecc
        assert spec.redundant_reads == 3 and spec.stuck_zero == 0.02
        assert spec.delay_s == 0.5 and spec.delay_prob == 0.1

    def test_with_and_without_dead_banks(self):
        spec = faults.FaultSpec(ber=0.1, dead_banks=(0,))
        assert spec.faulty
        fixed = spec.without_dead_banks()
        assert fixed.dead_banks == () and fixed.ber == 0.1
        assert not faults.FaultSpec().faulty

    def test_unknown_engine_message_lists_resilient(self):
        with pytest.raises(KeyError, match="resilient:tns"):
            sort_engine.sort(_data(8), engine="no-such-engine")


class TestInjector:
    def test_no_hook_outside_context(self):
        planes = bp.to_bitplanes(_data(32), 16, bp.UNSIGNED)
        assert bp.read_planes(planes) is planes
        assert faults.current() is None

    def test_deterministic_and_independent_reads(self):
        planes = bp.to_bitplanes(_data(32), 16, bp.UNSIGNED)
        spec = faults.FaultSpec(ber=0.05, seed=1)
        with faults.inject(spec):
            a1 = bp.read_planes(planes)
            a2 = bp.read_planes(planes)
        with faults.inject(spec):
            b1 = bp.read_planes(planes)
            b2 = bp.read_planes(planes)
        # same seed + read index -> same corruption; successive reads of
        # the same array see fresh noise (what majority voting relies on)
        assert np.array_equal(a1, b1) and np.array_equal(a2, b2)
        assert not np.array_equal(a1, a2)
        assert (a1 != planes).any()

    def test_stuck_cells_persist_across_reads(self):
        planes = bp.to_bitplanes(_data(32, seed=2), 16, bp.UNSIGNED)
        spec = faults.FaultSpec(stuck_one=0.3, seed=1)
        with faults.inject(spec):
            r1 = bp.read_planes(planes)
            r2 = bp.read_planes(planes)
        assert np.array_equal(r1, r2)  # persistent, not per-read
        assert (r1 != planes).any()

    def test_dead_bank_zeroes_its_slice(self):
        x = _data(64, seed=3)
        planes = bp.to_bitplanes(x, 16, bp.UNSIGNED)
        spec = faults.FaultSpec(dead_banks=(1,), banks=4)
        with faults.inject(spec):
            r = bp.read_planes(planes, banks=4)
        assert (r[:, 16:32] == 0).all()          # bank 1's 16 columns
        assert np.array_equal(r[:, :16], planes[:, :16])
        assert np.array_equal(r[:, 32:], planes[:, 32:])

    def test_majority_vote_beats_single_read(self):
        planes = bp.to_bitplanes(_data(256, seed=4), 16, bp.UNSIGNED)
        single = faults.FaultSpec(ber=0.05, seed=1)
        voted = single.with_(redundant_reads=5)
        with faults.inject(single):
            r1 = bp.read_planes(planes)
        with faults.inject(voted):
            r5 = bp.read_planes(planes)
        assert (r5 != planes).sum() < (r1 != planes).sum()

    def test_parity_ecc_corrects_sparse_flips(self):
        planes = bp.to_bitplanes(_data(256, seed=5), 16, bp.UNSIGNED)
        # ~1 flip per 5 columns: mostly single-bit-per-column errors, the
        # Hamming SEC regime
        spec = faults.FaultSpec(ber=0.01 / 16, seed=1)
        ctr = faults.FaultCounters()
        with faults.inject(spec.with_(parity_ecc=True), counters=ctr):
            r = bp.read_planes(planes)
        assert np.array_equal(r, planes)
        assert ctr.corrected > 0

    def test_digit_plane_faults(self):
        x = _data(64, seed=6)
        digits = bp.to_digitplanes(x, 16, bp.UNSIGNED, 2)
        with faults.inject(faults.FaultSpec(ber=0.05, seed=2)):
            r = bp.read_planes(digits, kind="digit", level_bits=2)
        assert r.shape == digits.shape
        assert (r != digits).any()
        assert r.max() < 4  # still radix-4 digits

    def test_counters_accumulate(self):
        planes = bp.to_bitplanes(_data(64), 16, bp.UNSIGNED)
        ctr = faults.FaultCounters()
        with faults.inject(faults.FaultSpec(ber=0.05, seed=1), counters=ctr):
            bp.read_planes(planes)
            bp.read_planes(planes)
        assert ctr.reads == 2 and ctr.faults_injected > 0

    def test_probe_dead_banks(self):
        spec = faults.FaultSpec(dead_banks=(0, 2), banks=4)
        assert faults.probe_dead_banks(spec) == [0, 2]
        assert faults.probe_dead_banks(faults.FaultSpec(banks=4)) == []


# ---------------------------------------------------------------------------
# Comparison-free verification.
# ---------------------------------------------------------------------------


class TestCheckSorted:
    def test_accepts_true_sort_and_rejects_swaps(self):
        for fmt, dtype in [(bp.UNSIGNED, np.uint16), (bp.TWOS, np.int16),
                           (bp.FLOAT, np.float32)]:
            rng = np.random.default_rng(1)
            x = rng.standard_normal(32).astype(dtype) if fmt == bp.FLOAT \
                else (rng.integers(-500, 500, 32).astype(dtype)
                      if fmt == bp.TWOS
                      else rng.integers(0, 1000, 32).astype(dtype))
            w = 32 if fmt == bp.FLOAT else 16
            for asc in (True, False):
                perm = np.argsort(x) if asc else np.argsort(x)[::-1]
                assert resilient.check_sorted(x, perm, width=w, fmt=fmt,
                                              ascending=asc)
                bad = perm.copy()
                bad[3], bad[11] = bad[11], bad[3]
                if x[bad[3]] != x[bad[11]]:  # swapped ties stay sorted
                    assert not resilient.check_sorted(
                        x, bad, width=w, fmt=fmt, ascending=asc)

    def test_prefix_boundary(self):
        x = np.array([5, 1, 9, 3, 7], dtype=np.uint8)
        assert resilient.check_sorted(x, [1, 3], width=8, fmt=bp.UNSIGNED)
        # sorted prefix that is NOT the global minimum set must fail
        assert not resilient.check_sorted(x, [3, 0], width=8,
                                          fmt=bp.UNSIGNED)

    def test_rejects_invalid_permutations(self):
        x = np.arange(8, dtype=np.uint8)
        assert not resilient.check_sorted(x, [0, 0, 1], width=8,
                                          fmt=bp.UNSIGNED)
        assert not resilient.check_sorted(x, [-1, 0], width=8,
                                          fmt=bp.UNSIGNED)

    def test_emission_quality(self):
        x = np.array([4, 2, 8, 6], dtype=np.uint8)
        good = np.argsort(x)
        assert resilient.emission_quality(x, good, width=8,
                                          fmt=bp.UNSIGNED) == 1.0
        half = np.array([1, 0, 2, 3])  # emits [2,4,8,6]: first two correct
        assert resilient.emission_quality(x, half, width=8,
                                          fmt=bp.UNSIGNED) == 0.5


# ---------------------------------------------------------------------------
# The resilient wrapper.
# ---------------------------------------------------------------------------


class TestResilientWrapper:
    def test_zero_fault_parity_all_engines(self):
        x = _data(48, seed=7)
        for name in sorted(sort_engine.engines()):
            if name.startswith(resilient.PREFIX):
                continue
            try:
                inner = sort_engine.sort(x, engine=name, k=2)
                res = sort_engine.sort(x, engine=resilient.PREFIX + name,
                                       k=2)
            except sort_engine.EngineUnsupported:
                continue
            assert np.array_equal(res.indices, inner.indices), name
            assert res.quality == 1.0 and not res.degraded, name
            assert res.repairs == 0 and res.retries == 0, name
            assert res.engine == resilient.PREFIX + name

    def test_dead_bank_plus_ber_repaired_exactly(self):
        x = _data(64, seed=3)
        spec = faults.FaultSpec(ber=0.01, dead_banks=(1,), banks=4, seed=3)
        with faults.inject(spec):
            res = sort_engine.sort(x, engine="resilient:tns")
        assert res.quality == 1.0 and not res.degraded
        assert res.repairs > 0 and res.retries > 0
        assert res.faults_injected > 0
        assert res.extra_cycles > 0  # migration + failed attempts
        assert np.array_equal(res.values, np.sort(x))

    def test_high_ber_degrades_gracefully(self):
        x = _data(64, seed=5)
        with faults.inject(faults.FaultSpec(ber=0.20, seed=5)):
            res = sort_engine.sort(x, engine="resilient:tns")  # no raise
        assert res.degraded
        assert res.quality is not None and 0.0 <= res.quality < 1.0
        assert res.retries > 0
        # a full permutation is still returned (best effort)
        assert sorted(res.indices.tolist()) == list(range(64))

    def test_operating_ber_keeps_quality(self):
        # the calibrated 3-bit multi-level cell's read noise
        x = _data(64, seed=4)
        with faults.inject(faults.FaultSpec(ber=dm.operating_ber(3),
                                            seed=4)):
            res = sort_engine.sort(x, engine="resilient:tns")
        assert res.quality >= 0.99 and not res.degraded

    def test_voting_alone_fixes_moderate_ber(self):
        x = _data(64, seed=8)
        with faults.inject(faults.FaultSpec(ber=0.01, seed=2)):
            res = sort_engine.sort(x, engine="resilient:tns")
        assert res.quality == 1.0 and res.repairs >= 1
        assert np.array_equal(res.values, np.sort(x))

    def test_batched_facade_aggregates_counters(self):
        xb = np.stack([_data(32, seed=s) for s in range(3)])
        with faults.inject(faults.FaultSpec(ber=0.01, seed=1)):
            res = sort_engine.sort(xb, engine="resilient:tns")
        assert res.indices.shape == (3, 32)
        assert res.quality == 1.0 and not res.degraded
        assert res.retries >= 3  # each instance repaired independently
        for b in range(3):
            assert np.array_equal(res.values[b], np.sort(xb[b]))

    def test_lazy_wrapping_of_late_engines(self):
        from repro.sort.registry import _REGISTRY, register

        @register("toy-late", mode="throughput")
        def _toy(x, *, width, fmt, k, ascending, level_bits, stop_after,
                 **kw):
            perm = np.argsort(x, kind="stable")
            if not ascending:
                perm = perm[::-1]
            from repro.sort.result import SortResult
            return SortResult(values=np.asarray(x)[perm], indices=perm,
                              engine="toy-late", fmt=fmt, width=width,
                              n=len(x))

        try:
            assert "resilient:toy-late" not in _REGISTRY
            res = sort_engine.sort(_data(16), engine="resilient:toy-late")
            assert res.quality == 1.0
        finally:
            _REGISTRY.pop("toy-late", None)
            _REGISTRY.pop("resilient:toy-late", None)

    def test_stop_after_prefix_verified(self):
        x = _data(64, seed=9)
        with faults.inject(faults.FaultSpec(ber=0.01, seed=4)):
            res = sort_engine.sort(x, engine="resilient:tns", stop_after=8)
        assert res.quality == 1.0
        assert np.array_equal(res.values, np.sort(x)[:8])


# ---------------------------------------------------------------------------
# Fault-tolerant multi-bank engine.
# ---------------------------------------------------------------------------


class TestMbFt:
    def test_clean_matches_tns(self):
        x = _data(64, seed=10)
        a = sort_engine.sort(x, engine="mb-ft", banks=4)
        b = sort_engine.sort(x, engine="tns")
        assert np.array_equal(a.indices, b.indices)
        assert a.quality == 1.0 and a.repairs == 0
        assert a.banks == 4

    def test_dead_bank_remaps_onto_survivors(self):
        x = _data(64, seed=3)
        spec = faults.FaultSpec(ber=0.01, dead_banks=(2,), banks=4, seed=7)
        with faults.inject(spec):
            res = sort_engine.sort(x, engine="mb-ft", banks=4)
        assert res.banks == 3                      # one bank lost
        assert res.quality == 1.0 and not res.degraded
        assert res.repairs > 0
        assert res.extra_cycles >= 16 * 16         # migration floor: 16
        assert np.array_equal(res.values, np.sort(x))  # numbers x W cycles

    def test_all_banks_dead_raises(self):
        x = _data(16)
        spec = faults.FaultSpec(dead_banks=(0, 1), banks=2)
        with faults.inject(spec):
            with pytest.raises(RuntimeError, match="dead"):
                sort_engine.sort(x, engine="mb-ft", banks=2)

    def test_remesh_path_with_forced_devices(self):
        """The true cross-array path: 4 host devices, one bank dead, the
        mesh is rebuilt over the 3 survivors (subprocess so the XLA flag
        does not leak)."""
        code = r"""
import sys; sys.path.insert(0, "src")
import numpy as np
from repro import sort as S
from repro.runtime import faults
x = np.random.default_rng(3).integers(0, 2**16, 63).astype(np.uint16)
spec = faults.FaultSpec(ber=0.005, dead_banks=(1,), banks=4, seed=3)
with faults.inject(spec):
    res = S.sort(x, engine="mb-ft", banks=4)
assert res.banks == 3, res.banks
assert res.quality == 1.0 and not res.degraded
assert np.array_equal(res.values, np.sort(x))
print("OK")
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# runtime-FT satellites (merged into runtime/faults.py).
# ---------------------------------------------------------------------------


class TestRuntimeFault:
    def test_retries_forward_kwargs(self):
        calls = []

        def step(a, *, b):
            calls.append((a, b))
            if len(calls) < 3:
                raise RuntimeError("transient")
            return a + b

        assert faults.run_step_with_retries(
            step, 1, b=2, retries=3, backoff_s=0.001) == 3
        assert calls == [(1, 2)] * 3

    def test_retries_exhaust(self):
        with pytest.raises(RuntimeError):
            faults.run_step_with_retries(
                lambda: (_ for _ in ()).throw(RuntimeError("x")),
                retries=1, backoff_s=0.001)

    def test_heartbeat_stop_joins(self):
        hb = faults.Heartbeat(interval_s=0.01, timeout_s=0.05)
        hb.start_self_beat("h")
        time.sleep(0.03)
        hb.stop(join_timeout_s=1.0)
        assert hb._thread is None
        assert hb.suspects() == []  # fresh beat, then cleanly stopped

    def test_fault_module_shim(self):
        # the old module path stays importable but warns and aliases the
        # canonical objects
        import importlib

        import repro.runtime.fault as shim
        with pytest.warns(DeprecationWarning):
            importlib.reload(shim)
        assert shim.Heartbeat is faults.Heartbeat
        assert shim.elastic_remesh is faults.elastic_remesh
        assert shim.run_step_with_retries is faults.run_step_with_retries
