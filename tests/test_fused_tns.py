"""Fused Pallas TNS kernel tests.

* Mechanical parity: the single-kernel episode engine reproduces the
  event-driven Python oracle's permutation, total cycles, digit reads and
  reload cycles across the engine-contract grid (every format, N that are
  and are not lane multiples, full sort vs top-m, LIFO depths including
  k=0, both directions).
* Observables: the in-kernel useful-DR count matches the while_loop
  machine's mixed-read count.
* Epilogue: the kernel's jit returns the counter lanes and, up to
  ``DEVICE_PERM_MAX`` emissions, the permutation's slots found on the
  device; both equal what the host reads off the raw rank ring and
  counter block.
* Autotune: the (block_rows, unroll) knobs never change results, and the
  table round-trips through save/load with mode-scoped nearest-cell
  lookup.
* Engine/serving integration: ``pallas-tns`` through the sort facade,
  and the dispatcher's autotune-derived wall prior.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sort as S
from repro.core import bitplane as bp
from repro.core import tns as jt
from repro.kernels import autotune, backend, fused_tns

RNG = np.random.default_rng(11)

FMT_DATA = {
    bp.UNSIGNED: (lambda n: RNG.integers(0, 256, n).astype(np.uint8), 8),
    bp.TWOS: (lambda n: RNG.integers(-128, 128, n).astype(np.int8), 8),
    bp.SIGNMAG: (lambda n: RNG.integers(-2**14, 2**14, n), 16),
    bp.FLOAT: (lambda n: RNG.standard_normal(n).astype(np.float16), 16),
}


def _batch(fmt, n, b):
    gen, width = FMT_DATA[fmt]
    return np.stack([gen(n) for _ in range(b)]), width


def _check_cell(fmt, n, b, *, k, stop_after, ascending=True, **knobs):
    x, width = _batch(fmt, n, b)
    m = n if stop_after is None else min(stop_after, n)
    got = fused_tns.fused_tns_sort(
        x, width=width, k=k, fmt=fmt, ascending=ascending,
        stop_after=stop_after, **knobs)
    want = jt.tns_sort_batch(x, width=width, k=k, fmt=fmt,
                             ascending=ascending, stop_after=stop_after)
    np.testing.assert_array_equal(np.asarray(got.perm)[:, :m],
                                  np.asarray(want.perm)[:, :m])
    np.testing.assert_array_equal(np.asarray(got.cycles),
                                  np.asarray(want.cycles))
    np.testing.assert_array_equal(np.asarray(got.drs),
                                  np.asarray(want.drs))
    np.testing.assert_array_equal(np.asarray(got.reload_cycles),
                                  np.asarray(want.reload_cycles))
    return got


class TestParity:
    @pytest.mark.parametrize("fmt", list(FMT_DATA))
    @pytest.mark.parametrize("n", [8, 24, 130])
    @pytest.mark.parametrize("k", [0, 2])
    def test_contract_grid(self, fmt, n, k):
        # 130 is deliberately not a multiple of the 128 lane width
        _check_cell(fmt, n, 3, k=k, stop_after=min(6, n))

    @pytest.mark.parametrize("fmt", [bp.UNSIGNED, bp.FLOAT])
    def test_full_sort(self, fmt):
        _check_cell(fmt, 12, 2, k=2, stop_after=None)

    def test_descending(self):
        _check_cell(bp.TWOS, 20, 2, k=2, stop_after=5, ascending=False)

    def test_single_element_and_ties(self):
        _check_cell(bp.UNSIGNED, 1, 2, k=2, stop_after=None)
        x = np.zeros((2, 16), np.uint8)        # all-tie drain path
        got = fused_tns.fused_tns_sort(x, width=8, k=2, fmt=bp.UNSIGNED)
        want = jt.tns_sort_batch(x, width=8, k=2, fmt=bp.UNSIGNED)
        np.testing.assert_array_equal(np.asarray(got.perm),
                                      np.asarray(want.perm))
        np.testing.assert_array_equal(np.asarray(got.cycles),
                                      np.asarray(want.cycles))

    def test_useful_dr_matches_digit_read_min_search(self):
        # with stop_after=1 the fused kernel runs exactly one min-search
        # episode, so its in-kernel mixed-read count must agree with the
        # independent digit_read kernel's useful-DR observable
        import jax.numpy as jnp
        from repro.kernels import digit_read
        x, width = _batch(bp.UNSIGNED, 64, 4)
        got = fused_tns.fused_tns_sort(x, width=width, k=2,
                                       fmt=bp.UNSIGNED, stop_after=1)
        planes = jnp.asarray(bp.to_bitplanes(x, width, bp.UNSIGNED))
        _, udr = digit_read.min_search(planes)
        np.testing.assert_array_equal(np.asarray(got.useful_drs),
                                      np.asarray(udr))

    def test_useful_dr_bounds_and_all_ties(self):
        x, width = _batch(bp.SIGNMAG, 48, 3)
        got = fused_tns.fused_tns_sort(x, width=width, k=2,
                                       fmt=bp.SIGNMAG, stop_after=12)
        assert np.all(np.asarray(got.useful_drs) <= np.asarray(got.drs))
        ties = np.zeros((2, 16), np.uint8)    # no read ever splits
        out = fused_tns.fused_tns_sort(ties, width=8, k=2,
                                       fmt=bp.UNSIGNED)
        assert np.all(np.asarray(out.useful_drs) == 0)


# the bare kernel launch: the rank ring and counter block the epilogue reads
_raw = jax.jit(fused_tns._launch,
               static_argnames=("k", "fmt", "ascending", "stop_after",
                                "block_rows", "unroll", "interpret"))

# (fmt, N, ascending); 130 is not a lane multiple, FLOAT has a sign plane
EPILOGUE_INPUTS = {
    "u8_n24": (bp.UNSIGNED, 24, True),
    "u8_n130": (bp.UNSIGNED, 130, True),
    "i8_n130_descending": (bp.TWOS, 130, False),
    "f16_n130": (bp.FLOAT, 130, True),
}


class TestEpilogue:
    @pytest.mark.parametrize("m", [1, 2, 6, 32, 33, None])
    @pytest.mark.parametrize("case", list(EPILOGUE_INPUTS))
    def test_device_slots_match_host_inversion(self, case, m):
        fmt, n, ascending = EPILOGUE_INPUTS[case]
        x, width = _batch(fmt, n, 3)
        x[1] = x[1, 0]                  # all ties: slots in index order
        planes = jnp.asarray(bp.to_bitplanes(x, width, fmt))
        sign = (jnp.asarray(bp.sign_plane(x, width, fmt))
                if fmt == bp.FLOAT else None)
        kw = dict(k=2, fmt=fmt, ascending=ascending, stop_after=m,
                  block_rows=None, unroll=1, interpret=None)
        rank, cnt = map(np.asarray, _raw(planes, sign, **kw))
        stop_n = n if m is None else min(m, n)
        want = fused_tns._rank_to_perm_np(rank)[:, :stop_n]
        out = np.asarray(fused_tns._fused_tns_rank(planes, sign, **kw))
        np.testing.assert_array_equal(out[:, :fused_tns._NOUT],
                                      cnt[:, :fused_tns._NOUT])
        np.testing.assert_array_equal(
            out[:, fused_tns._NOUT:],
            want if stop_n <= fused_tns.DEVICE_PERM_MAX else rank)
        got = fused_tns.fused_tns_sort(x, width=width, k=2, fmt=fmt,
                                       ascending=ascending, stop_after=m)
        np.testing.assert_array_equal(got.perm, want)
        np.testing.assert_array_equal(got.perm[1], np.arange(stop_n))
        for field, col in (("cycles", fused_tns._CYC),
                           ("drs", fused_tns._DRS),
                           ("reload_cycles", fused_tns._RLC),
                           ("useful_drs", fused_tns._UDR)):
            np.testing.assert_array_equal(getattr(got, field), cnt[:, col])


class TestAutotune:
    @pytest.mark.parametrize("knobs", [
        dict(block_rows=1, unroll=1),
        dict(block_rows=2, unroll=2),
        dict(block_rows=None, unroll=4),
    ])
    def test_knobs_never_change_results(self, knobs):
        rng = np.random.default_rng(3)
        x = rng.integers(0, 256, (4, 40)).astype(np.uint8)
        kw = dict(width=8, k=2, fmt=bp.UNSIGNED, stop_after=6)
        got = fused_tns.fused_tns_sort(x, **kw, **knobs)
        ref = fused_tns.fused_tns_sort(x, **kw)
        for field in ("perm", "cycles", "drs", "reload_cycles",
                      "useful_drs"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)),
                np.asarray(getattr(ref, field)))

    def test_table_roundtrip(self, tmp_path):
        mode = backend.mode()
        table = {autotune.cell_key("unsigned", 1024, 2, 64, mode):
                 {"block_rows": 16, "unroll": 2, "us": 100.0},
                 autotune.cell_key("float", 256, 8, 32, mode):
                 {"block_rows": 0, "unroll": 1, "us": 50.0}}
        path = tmp_path / "table.json"
        autotune.save_table(table, path)
        assert autotune.load_table(path) == table
        # exact hit
        assert autotune.best_params("unsigned", 1024, 2, 64,
                                    table=table) == \
            {"block_rows": 16, "unroll": 2}
        # nearest same-fmt cell (shape distance, not exact)
        assert autotune.best_params("unsigned", 512, 4, 64,
                                    table=table) == \
            {"block_rows": 16, "unroll": 2}
        # unknown fmt+mode falls back to defaults
        assert autotune.best_params("twos", 512, 4, 64, table=table) == \
            autotune.DEFAULT_PARAMS
        # a different mode never reuses this table's cells
        assert autotune.best_params("unsigned", 1024, 2, 64, table=table,
                                    mode="compiled-nonexistent") == \
            autotune.DEFAULT_PARAMS

    def test_committed_artifact_is_loadable(self):
        # the repo-root BENCH artifact doubles as the default table
        table = autotune.default_table()
        if not table:
            pytest.skip("no committed BENCH_pallas_tns.json")
        for key, row in table.items():
            assert {"block_rows", "unroll", "us"} <= set(row)


class TestEngineIntegration:
    def test_facade_matches_oracle(self):
        x, width = _batch(bp.UNSIGNED, 48, 1)
        res = S.sort(x[0], engine="pallas-tns", fmt=bp.UNSIGNED,
                     width=width, k=2, stop_after=8)
        ref = S.sort(x[0], engine="tns-oracle", fmt=bp.UNSIGNED,
                     width=width, k=2, stop_after=8)
        np.testing.assert_array_equal(np.asarray(res.indices)[:8],
                                      np.asarray(ref.indices)[:8])
        assert int(np.sum(res.cycles)) == int(np.sum(ref.cycles))

    def test_dispatch_wall_prior_reads_autotune_table(self, monkeypatch):
        from repro.serving import dispatch
        key = autotune.cell_key("unsigned", 1024, 2, 64)
        monkeypatch.setattr(
            autotune, "default_table",
            lambda: {key: {"block_rows": 0, "unroll": 1, "us": 1280.0}})
        # 1280us / (m=2 x b=64 emissions) = 10us per emission
        assert dispatch._pallas_tns_wall_prior() == pytest.approx(10.0)

    def test_env_stamp_fields(self):
        stamp = backend.env_stamp()
        assert set(stamp) == {"backend", "device_kind", "device_count",
                              "jax_version", "pallas_mode"}
        assert stamp["pallas_mode"] in ("compiled", "interpret", "jnp")
        assert stamp["device_count"] >= 1 and stamp["device_kind"]
