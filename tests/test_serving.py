"""Tests for the production serving subsystem (:mod:`repro.serving`):
simulated clock, admission queue (ranked on the repo's own engines),
budget-aware dispatch, and the continuous-batching orchestrator — all
deterministic, no wall-time sleeps anywhere in the loop.
"""
import time

import numpy as np
import pytest

from repro import serving
from repro.runtime import faults
from repro.serving import dispatch
from repro.serving.request import ENERGY, LATENCY, WALL
from repro.serving.metrics import percentile
from repro.serving.request import Status, priority_key


def _req(rid=0, n=32, m=None, priority=0, arrival_us=0.0, seed=0,
         dtype=np.uint16, ascending=True, **budget_kw):
    rng = np.random.default_rng((seed, rid))
    if np.issubdtype(dtype, np.floating):
        x = rng.standard_normal(n).astype(dtype)
    else:
        x = rng.integers(0, 1 << 16, n).astype(dtype)
    return serving.SortRequest(
        rid=rid, x=x, m=m, priority=priority, arrival_us=arrival_us,
        ascending=ascending, budget=serving.SortBudget(**budget_kw))


# ---------------------------------------------------------------------------
# Clock.
# ---------------------------------------------------------------------------


class TestClock:
    def test_simulated_advance(self):
        c = serving.SimulatedClock()
        assert c.now_us() == 0.0
        assert c.advance_us(2.5) == 2.5
        assert c.advance_cycles(400, 400e6) == pytest.approx(3.5)

    def test_negative_advance_raises(self):
        with pytest.raises(ValueError, match="negative"):
            serving.SimulatedClock().advance_us(-1.0)
        with pytest.raises(ValueError, match="freq_hz"):
            serving.SimulatedClock().advance_cycles(10, 0.0)

    def test_wall_clock_advances_itself(self):
        c = serving.WallClock()
        t0 = c.now_us()
        # advance_* are no-ops: wall time moves on its own
        assert c.advance_us(1e9) <= c.now_us() + 1e6
        assert c.now_us() >= t0


# ---------------------------------------------------------------------------
# Priority keys + queue.
# ---------------------------------------------------------------------------


class TestPriorityKey:
    def test_priority_dominates_age(self):
        lo = _req(rid=0, priority=0, arrival_us=0.0)
        hi = _req(rid=1, priority=1, arrival_us=0.0)
        # even maximal aging cannot beat the next priority class
        assert priority_key(hi, 0.0) > priority_key(lo, 1e12)

    def test_age_breaks_ties(self):
        old = _req(rid=0, priority=3, arrival_us=0.0)
        new = _req(rid=1, priority=3, arrival_us=5000.0)
        now = 10_000.0
        assert priority_key(old, now) > priority_key(new, now)

    def test_age_saturates(self):
        r = _req(rid=0, priority=7, arrival_us=0.0)
        assert priority_key(r, 1e15) < (1 << 32)


class TestRequestQueue:
    def test_pop_order_matches_numpy_baseline(self):
        rng = np.random.default_rng(0)
        now = 50_000.0
        reqs = [_req(rid=i, priority=int(rng.integers(0, 8)),
                     arrival_us=float(rng.uniform(0, now)))
                for i in range(12)]
        q = serving.RequestQueue(max_depth=64)
        for r in reqs:
            assert q.admit(r, now).accepted
        keys = [priority_key(r, now) for r in reqs]
        expect = sorted(range(len(reqs)), key=lambda i: (-keys[i], i))
        got = [r.rid for r in q.pop_batch(len(reqs), now)]
        assert got == expect

    def test_backpressure_without_shedding(self):
        q = serving.RequestQueue(max_depth=2, shed_low_priority=False)
        assert q.admit(_req(rid=0), 0.0).accepted
        assert q.admit(_req(rid=1), 0.0).accepted
        late = _req(rid=2, priority=7)
        d = q.admit(late, 0.0)
        assert not d.accepted and d.reason == "backpressure"
        assert late.status is Status.REJECTED
        assert late.reject_reason == "backpressure"

    def test_priority_shedding(self):
        q = serving.RequestQueue(max_depth=2)
        a, b = _req(rid=0, priority=0), _req(rid=1, priority=0)
        q.admit(a, 0.0), q.admit(b, 0.0)
        vip = _req(rid=2, priority=5)
        d = q.admit(vip, 0.0)
        assert d.accepted and d.reason == "shed"
        assert d.shed is a          # equal keys: lowest index is the victim
        assert a.status is Status.REJECTED and a.reject_reason == "shed"
        assert {r.rid for r in q.peek_all()} == {1, 2}

    def test_shedding_refuses_equal_priority(self):
        q = serving.RequestQueue(max_depth=1)
        q.admit(_req(rid=0, priority=3), 0.0)
        d = q.admit(_req(rid=1, priority=3), 0.0)
        assert not d.accepted and d.shed is None

    def test_expire_removes_past_deadline(self):
        q = serving.RequestQueue(max_depth=8)
        r1 = _req(rid=0, max_latency_us=5.0)
        r2 = _req(rid=1)
        q.admit(r1, 0.0), q.admit(r2, 0.0)
        gone = q.expire(10.0)
        assert gone == [r1] and r1.status is Status.EXPIRED
        assert q.peek_all() == [r2]

    def test_where_filter(self):
        q = serving.RequestQueue(max_depth=8)
        for i in range(4):
            q.admit(_req(rid=i, priority=i), 0.0)
        odd = q.pop_batch(4, 0.0, where=lambda r: r.rid % 2 == 1)
        assert [r.rid for r in odd] == [3, 1]
        assert q.depth == 2


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_percentile_nearest_rank(self):
        xs = [5.0, 1.0, 9.0, 3.0, 7.0]
        assert percentile(xs, 50) == np.percentile(
            xs, 50, method="inverted_cdf")
        assert percentile(xs, 99) == 9.0
        assert percentile([], 50) is None

    def test_ewma(self):
        e = serving.Ewma(alpha=0.5)
        assert e.value is None
        e.update(10.0)
        e.update(20.0)
        assert e.value == pytest.approx(15.0)


# ---------------------------------------------------------------------------
# Dispatcher.
# ---------------------------------------------------------------------------


class TestDispatcher:
    def test_candidates_exclude_oracle_and_respect_format(self):
        d = serving.Dispatcher()
        cands = d.candidates(_req(n=64))
        assert "tns-oracle" not in cands
        assert "bitslice" in cands
        # float rules out the unsigned-only bit-slice pipeline
        fcands = d.candidates(_req(n=64, dtype=np.float32))
        assert "bitslice" not in fcands and "tns" in fcands
        # so does a descending sort
        dcands = d.candidates(_req(n=64, ascending=False))
        assert "bitslice" not in dcands

    def test_pallas_topk_only_small_m(self):
        d = serving.Dispatcher()
        assert "pallas-topk" in d.candidates(_req(n=64, m=8))
        assert "pallas-topk" not in d.candidates(_req(n=64))

    def test_energy_objective_picks_ml(self):
        d = serving.Dispatcher()
        pick = d.select(_req(n=64, objective=ENERGY))
        assert pick.feasible and pick.engine == "ml"

    def test_wall_objective_picks_throughput_engine(self):
        d = serving.Dispatcher()
        pick = d.select(_req(n=64, m=8, objective=WALL))
        # every throughput engine starts from the same wall prior, so
        # the name breaks the tie
        assert pick.engine == "pallas-tns"

    def test_pallas_tns_starts_from_the_throughput_prior(self):
        d = serving.Dispatcher()
        req = _req(n=64, m=8)
        prior = dispatch._WALL_PRIOR_US["throughput"] * req.target
        for name in ("pallas-tns", "radix"):
            assert d.estimate(name, self._spec(name), req).wall_us == prior

    def test_infeasible_budget_degrades_to_best_effort(self):
        d = serving.Dispatcher()
        pick = d.select(_req(n=64, max_latency_us=1e-9))
        assert not pick.feasible and pick.reason == "best-effort"

    def test_fault_forces_verified_engines(self):
        d = serving.Dispatcher()
        with faults.inject(faults.FaultSpec(ber=0.01, seed=0)):
            cands = d.candidates(_req(n=64, quality_floor=0.99))
            pick = d.select(_req(n=64, quality_floor=0.99))
        # throughput engines bypass the faulted read path entirely
        assert "radix" not in cands and "pallas-topk" not in cands
        assert pick.engine.startswith("resilient:") or pick.engine == "mb-ft"
        assert pick.feasible

    def test_ewma_observation_steers_prediction(self):
        d = serving.Dispatcher()
        req = _req(n=64)
        before = d.estimate("tns", self._spec("tns"), req).cycles
        d.observe("tns", emissions=64, cycles=64 * 1000.0)
        after = d.estimate("tns", self._spec("tns"), req).cycles
        assert after == pytest.approx(64 * 1000.0)  # EWMA seeded by 1st obs
        assert after > before

    @staticmethod
    def _spec(name):
        from repro.sort.registry import available_engines
        return available_engines()[name]


# ---------------------------------------------------------------------------
# Orchestrator.
# ---------------------------------------------------------------------------


class TestOrchestrator:
    def _run(self, seed=0, n_requests=6, **cfg_kw):
        trace = serving.make_trace(n_requests, seed=seed, n=32,
                                   mean_gap_us=0.05)
        orch = serving.Orchestrator(
            clock=serving.SimulatedClock(),
            cfg=serving.OrchestratorConfig(chunk=16, **cfg_kw))
        return orch.run(trace)

    def test_deterministic_and_sleepless(self, monkeypatch):
        # the loop must never touch wall-time sleeps: make any sleep fatal
        def no_sleep(_):
            raise AssertionError("serving loop called time.sleep")
        monkeypatch.setattr(time, "sleep", no_sleep)
        a = self._run()
        b = self._run()
        a.pop("wall_ms"), b.pop("wall_ms")
        assert a == b
        assert a["completed"] == a["accepted"] == 6
        assert a["sim_us"] > 0

    def test_full_completion_and_metrics(self):
        rep = self._run(n_requests=8)
        assert rep["completed"] == 8 and rep["failed"] == 0
        assert rep["p50_latency_us"] <= rep["p99_latency_us"]
        assert rep["peak_batch_occupancy"] >= 1
        assert sum(rep["engines"].values()) == 8
        assert rep["throughput_elems_per_us"] > 0

    def test_deadline_expiry_sheds_queued_request(self):
        clock = serving.SimulatedClock()
        orch = serving.Orchestrator(clock=clock)
        req = _req(rid=0, max_latency_us=5.0)
        assert orch.submit(req)
        clock.advance_us(10.0)          # deadline passes while queued
        orch.tick()
        assert req.status is Status.EXPIRED
        assert orch.stats.expired == 1
        assert orch.queue.depth == 0 and not orch.batch

    def test_step_failure_cooldown_then_fail(self, monkeypatch):
        import repro.sort as sort_mod
        def boom(*a, **kw):
            raise faults.FaultError("injected step failure")
        monkeypatch.setattr(sort_mod, "sort", boom)
        orch = serving.Orchestrator(
            clock=serving.SimulatedClock(),
            cfg=serving.OrchestratorConfig(cooldown_ticks=2,
                                           max_step_retries=1))
        req = _req(rid=0)
        orch.submit(req)
        orch.tick()                     # failure 1: run rule goes on cooldown
        assert orch._cooldown.get("run", 0) > 0
        occupancy_during_cooldown = len(orch.batch)
        orch.tick()                     # cooldown tick (run skipped)
        assert len(orch.batch) == occupancy_during_cooldown
        orch.tick()                     # retry > max_step_retries: cohort fails
        assert req.status is Status.FAILED
        assert orch.stats.failed == 1 and not orch.batch

    def test_device_error_propagates(self, monkeypatch):
        # an error of the program or the chip (XLA's JaxRuntimeError is a
        # RuntimeError) is never turned into a FAILED request
        import repro.sort as sort_mod
        def boom(*a, **kw):
            raise RuntimeError("device error")
        monkeypatch.setattr(sort_mod, "sort", boom)
        orch = serving.Orchestrator(clock=serving.SimulatedClock())
        req = _req(rid=0)
        orch.submit(req)
        with pytest.raises(RuntimeError, match="device error"):
            orch.tick()
        assert orch.stats.failed == 0 and not orch._inflight

    def test_backpressure_counts_rejections(self):
        clock = serving.SimulatedClock()
        orch = serving.Orchestrator(
            clock=clock,
            cfg=serving.OrchestratorConfig(queue_depth=1))
        # same priority everywhere: no shedding, pure backpressure
        assert orch.submit(_req(rid=0, priority=3))
        assert not orch.submit(_req(rid=1, priority=3))
        assert orch.stats.accepted == 1 and orch.stats.rejected == 1

    @pytest.fixture(scope="class")
    def saturated(self):
        """Continuous batching and the one-shot loop on one saturated
        trace (arrivals far faster than service)."""
        def trace():
            return serving.make_trace(12, seed=0, n=32, mean_gap_us=0.05)
        orch = serving.Orchestrator(
            clock=serving.SimulatedClock(),
            cfg=serving.OrchestratorConfig(chunk=16))
        return orch.run(trace()), serving.oneshot_loop(trace())

    def test_continuous_batching_beats_oneshot(self, saturated):
        # a batched step costs the max of its members' cycles, the
        # one-shot loop the sum
        cont, ones = saturated
        assert cont["completed"] == cont["accepted"] == 12
        assert cont["failed"] == 0
        assert cont["throughput_elems_per_us"] > \
            ones["throughput_elems_per_us"]

    def test_dispatch_spreads_over_engines(self, saturated):
        assert len(saturated[0]["engines"]) >= 3

    def test_faulted_trace_uses_verified_engines(self):
        trace = serving.make_trace(6, seed=1, n=32, mean_gap_us=0.05,
                                   classes=("bulk-latency", "float-latency"),
                                   quality_floor=0.99)
        orch = serving.Orchestrator(
            clock=serving.SimulatedClock(),
            cfg=serving.OrchestratorConfig(chunk=16))
        with faults.inject(faults.FaultSpec(ber=0.01, seed=0)):
            rep = orch.run(trace)
        assert rep["completed"] == rep["accepted"] == 6
        assert all(e.startswith("resilient:") or e == "mb-ft"
                   for e in rep["engines"])

    def test_oneshot_loop_equal_mix(self):
        trace = serving.make_trace(4, seed=0, n=32, mean_gap_us=0.05)
        rep = serving.oneshot_loop(trace)
        assert rep["completed"] == 4
        assert rep["throughput_elems_per_us"] > 0
