"""The open loop: arrivals from the seed, latency timed from the due time,
the drain and what counts in ``failed``, and a service cell made of data
files alone, run on the CPU through the orchestrator entry."""
from __future__ import annotations

import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench.harness import CompileCounter, Run, measure, run_open
from bench.references import sort_reference as ref
from bench.spec import BENCH, ROOT, load_cell, load_module
from bench.traffic import arrivals

NAME = "svc.open"
CONFIG = {"source": "a multi-tenant sort service (test)",
          "entry": "orchestrator", "orchestrator": {}, "widths": [8, 32],
          "ascending": True, "counters": None, "reference": "sort_reference"}
TRAFFIC = {"loop": "open", "generator": "service", "rate": 20,
           "burst_factor": 4, "burst_s": 0.3, "calm_s": 1.0, "drain_s": 60,
           "pool": 48, "tenants": 8, "zipf_s": 0.99, "profile_seed": 0,
           "n_range": [32, 64], "m_mix": [[1, 0.4], [8, 0.2], [32, 0.2],
                                          [None, 0.2]],
           "priorities": [0, 7], "deadline_ms": None, "objective": "latency"}
SERVE_METRICS = {"serve.lateness_p95_ms": "ms", "serve.completed_share": "%",
                 "serve.compiles_in_window": "count"}


def service_root(tmp_path, **traffic):
    """A checkout's data files for one open-loop cell: a configuration, a
    traffic file and a ``workloads`` entry, nothing else."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "svc", "source": CONFIG["source"],
                        "file": "bench/configs/svc.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": NAME, "config": "svc", "traffic": "svc",
                          "chips": 1, "why": "test"}]
    spec["per_layer"] = [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": "serving", "moves": "p95_ms", "workloads": [NAME]}
        for n, u in SERVE_METRICS.items()]
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench/configs/svc.json").write_text(json.dumps(CONFIG))
    (tmp_path / "bench/traffic/svc.json").write_text(
        json.dumps({**TRAFFIC, **traffic}))
    return tmp_path


def answer(req):
    perm = ref.permutation(req.x, True, req.stop_after)
    return ref.ControlResult(perm, np.take_along_axis(req.x, perm, -1))


# -- arrivals

@pytest.mark.parametrize("traffic", [
    {"rate": 50.0},
    {"rate": 50.0, "burst_factor": 4, "burst_s": 0.5, "calm_s": 2.0},
    {"rate": 400.0, "burst_factor": 8, "burst_s": 0.2, "calm_s": 1.0},
])
def test_a_schedule_hits_its_rate_and_burst_share(traffic):
    seconds = 2000.0
    s = arrivals.schedule(traffic, 2 ** 31 + 5, seconds)
    n = len(s.due)
    assert np.all(np.diff(s.due) > 0) and 0 <= s.due[0] and s.due[-1] < seconds
    # a Poisson count's spread, widened by the spells' own
    assert abs(n / seconds - traffic["rate"]) < 0.03 * traffic["rate"]
    f = traffic.get("burst_factor", 1)
    if f == 1:
        assert not s.burst.any()
        return
    b, c = traffic["burst_s"], traffic["calm_s"]
    share = f * b / (c + f * b)      # of the requests, due in a burst
    assert abs(s.burst.mean() - share) < 0.03
    calm, burst = arrivals.rates(traffic)
    assert burst == pytest.approx(f * calm)
    assert (calm * c + burst * b) / (c + b) == pytest.approx(traffic["rate"])


def test_a_schedule_is_a_function_of_the_seed():
    t = {"rate": 100, "burst_factor": 4, "burst_s": 0.5, "calm_s": 2.0}
    a, b = arrivals.schedule(t, 11, 10), arrivals.schedule(t, 11, 10)
    assert np.array_equal(a.due, b.due)
    c = arrivals.schedule(t, 12, 10)
    assert not np.array_equal(a.due[:10], c.due[:10])
    # the gaps are one multiset in another order: the counts stay close
    counts = [len(arrivals.schedule(t, s, 10).due) for s in range(12)]
    assert max(counts) - min(counts) < 0.05 * np.mean(counts)


def test_exponentials_are_a_fixed_multiset():
    rng = np.random.default_rng(0)
    a, b = arrivals.exponentials(1000, rng), arrivals.exponentials(1000, rng)
    assert np.array_equal(np.sort(a), np.sort(b)) and not np.array_equal(a, b)
    assert a.mean() == pytest.approx(1.0, abs=0.01)


def mmpp(traffic, seed, seconds):
    """The two-state MMPP with fresh exponential draws: what the
    schedule's fixed quantiles stand in for."""
    rng = np.random.default_rng(seed)
    calm, burst = arrivals.rates(traffic)
    b, c = traffic["burst_s"], traffic["calm_s"]
    due, t, in_burst = [], 0.0, rng.random() < b / (b + c)
    while t < seconds:
        end = min(t + rng.exponential(b if in_burst else c), seconds)
        rate = burst if in_burst else calm
        while (t := t + rng.exponential(1 / rate)) < end:
            due.append(t)
        t, in_burst = end, not in_burst
    return np.array(due)


def fifo_p95(due, service_s):
    """p95 of the time in system of a FIFO server taking ``service_s``
    for each request."""
    free, lat = 0.0, []
    for d in due:
        free = max(d, free) + service_s
        lat.append(free - d)
    return np.percentile(lat, 95)


def test_the_schedule_keeps_the_mmpps_mean_tail_and_cuts_its_spread():
    """Through a queue at 4/5 load, over 200 seeds: the quantile schedule
    gives the mean p95 of true MMPP draws, at half their spread from seed
    to seed."""
    t = {"rate": 8.0, "burst_factor": 4, "burst_s": 0.5, "calm_s": 2.0}
    seeds = range(2 ** 31, 2 ** 31 + 200)
    ours = [fifo_p95(arrivals.schedule(t, s, 10.0).due, 0.1) for s in seeds]
    true = [fifo_p95(mmpp(t, s, 10.0), 0.1) for s in seeds]
    assert np.mean(ours) == pytest.approx(np.mean(true), rel=0.15)

    def iqr(v):
        q = np.percentile(v, [25, 50, 75])
        return (q[2] - q[0]) / q[1]
    assert iqr(ours) < 0.7 * iqr(true)


# -- the loop

class Stalling:
    """Answers each submitted request at its next step, from the
    reference; the first step past ``after`` seconds stalls for
    ``stall`` seconds."""

    def __init__(self, after, stall):
        self.after, self.stall, self.todo = after, stall, []
        self.t0 = time.perf_counter()
        self.stalled = None     # (start, end) on the host clock

    def submit(self, rid, req):
        self.todo.append((rid, req))

    def busy(self):
        return bool(self.todo)

    def step(self):
        t = time.perf_counter()
        if self.stalled is None and t - self.t0 >= self.after:
            time.sleep(self.stall)
            self.stalled = (t, time.perf_counter())
        out = [(rid, answer(req), None) for rid, req in self.todo]
        self.todo = []
        return out


def test_a_stall_shows_in_the_latency_of_requests_due_during_it():
    pool = [SimpleNamespace(x=np.arange(16, dtype=np.uint8)[None, ::-1],
                            stop_after=1)]
    due = np.arange(0.0, 1.0, 0.004)
    svc = Stalling(after=0.3, stall=0.3)
    w = run_open(svc, pool, due, drain_s=1.0, seconds=1.0,
                 counter=CompileCounter())
    lo, hi = svc.stalled
    assert len(w.calls) == len(due) == len(w.lateness)
    assert all(c.status is None and c.error is None for c in w.calls)
    lat = np.array([c.latency_s for c in w.calls])
    at = w.start + due
    during = (at > lo + 0.01) & (at < hi - 0.01)
    assert during.sum() > 50
    # timed from due: each waited at least until the stall ended
    assert np.all(lat[during] >= hi - at[during] - 1e-3)
    assert lat[during].max() > 0.25
    # and the generator was late by as much, which it reports
    late = np.array(w.lateness)
    assert np.all(late[during] >= hi - at[during] - 1e-3)
    assert np.all(lat >= late)
    assert np.median(lat[~during]) < 0.1
    assert w.end <= w.start + 1.0 + 0.5


class Stops:
    """Answers each submitted request at its next step, from the
    reference, until ``after`` seconds; from then on it holds them all."""

    def __init__(self, after):
        self.after, self.todo, self.held = after, [], []
        self.t0 = time.perf_counter()

    def submit(self, rid, req):
        self.todo.append((rid, req))

    def busy(self):
        return bool(self.todo or self.held)

    def step(self):
        time.sleep(0.001)
        if time.perf_counter() - self.t0 >= self.after:
            self.held += self.todo
            self.todo = []
        out = [(rid, answer(req), None) for rid, req in self.todo]
        self.todo = []
        return out


def test_a_service_that_stops_answering_reads_slower_and_later():
    """The rate and the tail see the requests that never came back: the
    window runs to the drain's end, and each such request waits in the
    tail from its due time to there."""
    pool = [SimpleNamespace(x=np.arange(16, dtype=np.uint8)[None, ::-1],
                            stop_after=1)]
    due = np.arange(0.0, 1.0, 0.01)
    p95, rate = {}, {}
    for name, svc in (("healthy", Stops(after=1e9)),
                      ("stops", Stops(after=0.5))):
        w = run_open(svc, pool, due, drain_s=0.4, seconds=1.0,
                     counter=CompileCounter())
        run = Run(pool, w, 0.0)
        p95[name] = load_module(BENCH / "metrics" / "p95_ms.py").read(run)
        rate[name] = load_module(BENCH / "metrics" / "elems_per_s.py").read(
            run)
        assert run.window_s >= 1.0
        assert all(c.latency_s is not None for c in w.calls)
    assert len(run.done) < 0.7 * len(due)
    assert run.window_s >= 1.0 + 0.4
    assert rate["stops"] < 0.6 * rate["healthy"]
    assert rate["healthy"] == pytest.approx(len(due) * 16 / 1.0, rel=0.05)
    # the held requests wait from their due times to the drain's end
    assert p95["healthy"] < 100 and p95["stops"] > 400


class Ends:
    """rid % 4: 0 rejected, 1 expired, 2 never answered, 3 answered."""

    def __init__(self, cfg=None):
        self.todo, self.held = [], set()

    def submit(self, rid, req):
        self.todo.append((rid, req))

    def busy(self):
        return bool(self.todo) or bool(self.held)

    def step(self):
        time.sleep(0.001)
        out = []
        for rid, req in self.todo:
            if rid < 0 or rid % 4 == 3:       # set-up's, and every 4th
                out.append((rid, answer(req), None))
            elif rid % 4 == 2:
                self.held.add(rid)
            else:
                out.append((rid, None, "rejected" if rid % 4 == 0
                            else "expired"))
        self.todo = []
        return out


def test_the_drain_ends_the_loop_and_what_never_came_counts_as_failed(
        tmp_path):
    cell = load_cell(NAME, service_root(tmp_path, rate=100, drain_s=0.3))
    cell = cell._replace(entry=SimpleNamespace(make=Ends))
    t0 = time.perf_counter()
    run, line = measure(cell, 3, 0.5, False, t0)
    n = line["attempted"]
    ends = [c.status for c in run.window.calls]
    assert n == len(ends) > 20
    assert ends.count("unfinished") == len(range(2, n, 4))
    assert ends.count("rejected") == len(range(0, n, 4))
    assert ends.count("expired") == len(range(1, n, 4))
    # they are failures of the service, not wrong answers
    assert line["correct"] and line["failed"] == n - len(range(3, n, 4))
    assert line["checks"]["rows_wrong"]["value"] == 0
    assert line["checks"]["calls_raised"]["value"] == 0
    # the drain waited no longer than it may
    assert time.perf_counter() - run.window.start < 0.5 + 0.3 + 0.5


def test_a_service_that_raises_is_not_correct(tmp_path):
    cell = load_cell(NAME, service_root(tmp_path, rate=100, drain_s=0.3))
    pool = cell.generator.make_pool(cell.cfg, cell.traffic, 4)
    shapes = len({(r.x.shape, r.x.dtype.str, r.stop_after) for r in pool})
    calls = []

    def breaks(x, stop_after):
        calls.append(1)
        if len(calls) > shapes + 3:     # set-up passes, then it breaks
            raise RuntimeError("device lost")
        return answer(SimpleNamespace(x=x, stop_after=stop_after))
    run, line = measure(cell, 4, 0.5, False, time.perf_counter(),
                        call=breaks)
    assert not line["correct"]
    assert line["checks"]["calls_raised"]["value"] > 0
    assert line["failed"] == line["attempted"] - len(run.done)


# -- a service cell from data files alone

def test_a_service_cell_is_data_files_alone(tmp_path):
    cell = load_cell(NAME, service_root(tmp_path))
    assert cell.entry.__file__ == str(BENCH / "entries" / "orchestrator.py")
    assert cell.generator.__file__ == str(BENCH / "traffic" / "service.py")
    assert [m.name for m in cell.per_layer] == list(SERVE_METRICS)
    # the reference in the program's place: every answer right, and the
    # result line, with the per-layer readers finding numbers
    run, line = measure(cell, 2 ** 31 + 9, 0.5, False, time.perf_counter(),
                        call=lambda x, m: answer(SimpleNamespace(
                            x=x, stop_after=m)))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"elems_per_s", "p95_ms", "setup_s"}
    for m in cell.per_layer:
        assert m.reader.read(run) is not None, m.name
    assert m.reader.read(run) >= 0
    assert cell.per_layer[1].reader.read(run) == 100.0


@pytest.mark.parametrize("fault", ["control", "answer_altered"])
def test_a_wrong_answer_in_the_open_loop_is_not_correct(tmp_path, fault):
    cell = load_cell(NAME, service_root(tmp_path))
    control = ref.control_call(cell.cfg)

    def call(x, m):
        if fault == "control":
            return control(x, m)
        res = answer(SimpleNamespace(x=x, stop_after=m))
        res.indices[0, 0] = (res.indices[0, 0] + 1) % x.shape[-1]
        return res
    _, line = measure(cell, 6, 0.5, False, time.perf_counter(), call=call)
    assert not line["correct"]
    assert line["checks"]["rows_wrong"]["value"] > 0


def test_the_orchestrator_serves_a_service_cell_on_the_cpu(tmp_path):
    """The orchestrator entry end to end: set-up sends each shape through
    it, the open loop drives it on the wall clock, and every answer is
    compared with the reference."""
    cell = load_cell(NAME, service_root(tmp_path))
    run, line = measure(cell, 8, 1.0, False, time.perf_counter())
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == len(run.done) > 5
    assert line["checks"]["rows_wrong"]["value"] == 0
    ms = {run.pool[c.pool_index].stop_after for c in run.done}
    assert None in ms and ms & {1, 8, 32}
    widths = {run.pool[c.pool_index].x.dtype for c in run.done}
    assert widths == {np.dtype(np.uint8), np.dtype(np.uint32)}
    assert all(c.latency_s > 0 for c in run.done)
