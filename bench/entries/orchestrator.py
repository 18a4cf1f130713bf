"""The open loop's entry for the sort service: the serving orchestrator
(``repro.serving``) on the wall clock.

``make(cfg)`` builds ``Orchestrator(clock=WallClock(),
cfg=OrchestratorConfig(**cfg["orchestrator"]))`` and drives it through
its public API alone: ``submit``, ``tick`` and the status and indices of
the ``SortRequest`` it was handed.

* ``submit(rid, request)`` makes a ``SortRequest``: the request's row, its
  ``m``, priority and budget objective, ``arrival_us`` the orchestrator's
  clock now, the configuration's direction, and ``SortBudget(
  max_latency_us=…)`` from the request's wall-clock deadline.
* ``step()`` is one ``tick()``.  It returns every request that reached an
  end since the last step, as ``(rid, result, status)``: a DONE request
  as an ``Answer`` (its indices, and the row's values at them), with
  status None; any other end as no result and the status's name
  (``rejected``, ``expired``, ``failed``).
* ``busy()``: whether a submitted request has not been returned yet.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Answer(NamedTuple):
    indices: np.ndarray   # (1, m)
    values: np.ndarray    # (1, m): the row's keys at those indices


class Service:
    def __init__(self, cfg: dict):
        from repro import serving
        self._serving = serving
        self.orch = serving.Orchestrator(
            clock=serving.WallClock(),
            cfg=serving.OrchestratorConfig(**cfg["orchestrator"]))
        self.ascending = cfg["ascending"]
        self.live: dict[int, object] = {}   # rid -> its SortRequest

    def submit(self, rid: int, request) -> None:
        s = self._serving
        deadline = request.deadline_ms
        req = s.SortRequest(
            rid=rid, x=request.x[0], m=request.stop_after,
            priority=request.priority, arrival_us=self.orch.clock.now_us(),
            ascending=self.ascending,
            budget=s.SortBudget(
                max_latency_us=None if deadline is None else deadline * 1e3,
                objective=request.objective))
        self.live[rid] = req
        self.orch.submit(req)

    def busy(self) -> bool:
        return bool(self.live)

    def step(self) -> list[tuple[int, Answer | None, str | None]]:
        self.orch.tick()
        status = self._serving.Status
        out = []
        for rid, req in list(self.live.items()):
            if req.status in (status.QUEUED, status.RUNNING):
                continue
            del self.live[rid]
            if req.status is status.DONE:
                idx = np.asarray(req.indices)[None, :]
                out.append((rid, Answer(idx, req.x[idx]), None))
            else:
                out.append((rid, None, req.status.value))
        return out


def make(cfg: dict) -> Service:
    return Service(cfg)
