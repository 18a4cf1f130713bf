"""Edge cases and golden regression for the cost model
(:mod:`repro.core.cost`) — the serving dispatcher leans on
``operating_point`` for every estimate, so its failure modes must be
loud and its anchors exact.
"""
import math

import numpy as np
import pytest

from repro.core import cost


# Cycles implied by the published anchors: round(freq * 1024 / (thpt*1e6))
# — the counts the cycle-faithful engines reproduce at n=1024, w=32.
IMPLIED_CYCLES = {"bts": 32768, "tns": 2995, "mb": 2642, "bs": 1820,
                  "ml": 1712}

# Anchor call kwargs per strategy: mb's anchor is the 2-bank point and
# ml's is the 4-bit-cell point.
ANCHOR_KW = {"mb": dict(banks=2), "ml": dict(level_bits=4)}


class TestOperatingPointValidation:
    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError, match="unknown strategy"):
            cost.operating_point("quantum")

    def test_unknown_strategy_lists_known(self):
        with pytest.raises(ValueError, match="bts.*ml.*tns"):
            cost.operating_point("nope")

    @pytest.mark.parametrize("bad", [0, -1, -1024])
    def test_n_must_be_positive(self, bad):
        with pytest.raises(ValueError, match="n must be"):
            cost.operating_point("tns", n=bad)

    def test_w_and_banks_must_be_positive(self):
        with pytest.raises(ValueError, match="w must be"):
            cost.operating_point("tns", w=0)
        with pytest.raises(ValueError, match="banks must be"):
            cost.operating_point("mb", banks=0)

    def test_n_equals_one(self):
        # degenerate single-number "sort" still has a sane physical point
        for s in sorted(cost.TABLE_S5):
            p = cost.operating_point(s, n=1)
            assert p.freq_hz > 0 and math.isfinite(p.freq_hz)
            assert p.area_mm2 > 0 and p.power_w > 0

    def test_w_not_multiple_of_slice_width(self):
        # w=24 is not a multiple of the 8-bit slice the BS pipeline uses;
        # the operating point must still be well-defined (the engines pad)
        p = cost.operating_point("bs", n=256, w=24)
        assert p.w_ref == 24
        assert p.freq_hz > 0 and math.isfinite(p.freq_hz)

    def test_k_none_uses_anchor_depth(self):
        for s in sorted(cost.TABLE_S5):
            p = cost.operating_point(s, **ANCHOR_KW.get(s, {}))
            assert p.k_ref == cost.TABLE_S5[s].k_ref


class TestGoldenTableS5:
    """sort_metrics at the implied anchor cycles reproduces every published
    Table S5 column (throughput, area-eff, energy-eff, FoM)."""

    @pytest.mark.parametrize("strategy", sorted(cost.TABLE_S5))
    def test_anchor_row(self, strategy):
        pub = cost.table_s5_published()[strategy]
        point = cost.operating_point(strategy, n=1024, w=32,
                                     **ANCHOR_KW.get(strategy, {}))
        assert point.freq_hz == pytest.approx(pub["freq"], rel=1e-9)
        m = cost.sort_metrics(IMPLIED_CYCLES[strategy], 1024, point)
        # published values are rounded to ~5 significant digits; the
        # implied cycle count adds at most one part in ~1700 of rounding
        assert m.throughput_num_per_us == pytest.approx(pub["thpt"],
                                                        rel=2e-3)
        assert m.area_eff == pytest.approx(pub["area_eff"], rel=2e-3)
        assert m.energy_eff == pytest.approx(pub["energy_eff"], rel=2e-3)
        assert m.fom == pytest.approx(pub["fom"], rel=6e-3)

    def test_bts_published_example(self):
        # the docstring's worked example: 1024/(32768 cyc / 625 MHz)
        point = cost.operating_point("bts")
        m = cost.sort_metrics(32768, 1024, point)
        assert m.throughput_num_per_us == pytest.approx(19.53, abs=0.01)


# Engine calls at the Table S5 operating points (1024 x 32-bit).
S5_ENGINES = {"tns": dict(k=4), "mb": dict(k=6, banks=2),
              "ml": dict(k=1, level_bits=4),
              "bitslice": dict(k=4, slice_widths=[8, 24])}


@pytest.fixture(scope="module")
def s5_metrics():
    """The engines' metrics, from cycles counted on 1,024 seeded uniform
    32-bit keys."""
    from repro import sort
    x = np.random.default_rng(0).integers(0, 2**32, 1024, dtype=np.uint64)
    return {name: sort.sort(x, engine=name, width=32, fmt="unsigned",
                            **kw).metrics()
            for name, kw in S5_ENGINES.items()}


class TestHeadlineClaims:
    """The abstract's ranges against the ASIC merge sorter: "3.32x~7.70x
    speedup, 6.23x~183.5x energy efficiency improvement and 2.23x~7.43x
    area reduction", over the TNS / CA-TNS configurations.  The best
    speedup and energy ratios reach the claim (at most 15% past its top);
    the smallest area reduction lies inside it."""

    @pytest.mark.parametrize("claim", ["speedup", "energy", "area"])
    def test_against_asic_merge(self, s5_metrics, claim):
        asic = cost.REFERENCE_SYSTEMS["asic_merge"]
        if claim == "speedup":
            best = max(m.throughput_num_per_us / asic["thpt"]
                       for m in s5_metrics.values())
            assert 3.32 <= best <= 7.70 * 1.15
        elif claim == "energy":
            best = max(m.energy_eff / asic["energy_eff"]
                       for m in s5_metrics.values())
            assert 100.0 <= best <= 183.5 * 1.15
        else:
            asic_area = asic["thpt"] / 1e3 / asic["area_eff"]     # mm^2
            least = min(asic_area / m.area_mm2
                        for m in s5_metrics.values())
            assert 2.0 <= least <= 7.43
