"""Unified sort-engine subsystem: one front door for every strategy the
memristor substrate can be reconfigured into (paper §2.2-2.3), plus the
jittable in-model dispatchers the serving stack uses.

    from repro import sort
    res = sort.sort(x, engine="tns", k=4)       # cycle-faithful, observables
    res = sort.sort(xb, engine="radix")         # throughput, batched
    sort.engines()                              # the registry

New engines register via ``repro.sort.register`` and automatically join
the facade, the parity tests and the benchmark sweeps.
"""
from repro.sort.api import (TOPK_ENGINES, engines, prune_mask, sort, topk,
                            topk_mask)
from repro.sort.registry import (EngineSpec, EngineUnsupported,
                                 available_engines, get_engine, register)
from repro.sort.result import SortResult

__all__ = [
    "EngineSpec", "EngineUnsupported", "SortResult", "TOPK_ENGINES", "available_engines",
    "engines", "get_engine", "prune_mask", "register", "sort", "topk",
    "topk_mask",
]
