"""Engine protocol + registry for the unified sort subsystem.

The paper's headline claim is *reconfigurability*: one memristor substrate
runs TNS, the CA-TNS variants and the application workloads by swapping
peripheral configuration, not hardware.  This registry is the software
image of that: every sorting strategy registers one callable behind a
shared contract, and the front door (:func:`repro.sort.sort`) dispatches
by name.  Adding an engine — a sharded CA-TNS, an approximate top-k, a new
dtype — is one ``@register(...)`` away and automatically inherits the
facade, the parity test suite and the benchmark sweep.

Engine contract::

    fn(x, *, width, fmt, k, ascending, level_bits, stop_after, **kw)
        -> SortResult

``x`` is a host ndarray, shape (N,) or (B, N) when the engine declares
``supports_batch``.  Engines in ``latency`` mode are cycle-faithful (they
report the paper's cycles/DRs observables); ``throughput`` engines are the
TPU-native vectorized forms and report no cycle counts.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

from repro.core import bitplane as bp

ALL_FORMATS = (bp.UNSIGNED, bp.TWOS, bp.SIGNMAG, bp.FLOAT)


class EngineUnsupported(NotImplementedError):
    """An engine refuses an input outside what it was built for (a top-m
    cap, a key width, a level count).  Callers that sweep engines skip on
    this class only: a compiler or device error is never a refusal."""


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    name: str
    fn: Callable
    mode: str                       # "latency" | "throughput"
    strategy: Optional[str]         # cost-model anchor key (Table S5) | None
    formats: Tuple[str, ...] = ALL_FORMATS
    supports_stop_after: bool = False
    supports_batch: bool = False
    description: str = ""

    @property
    def latency_mode(self) -> bool:
        return self.mode == "latency"


_REGISTRY: Dict[str, EngineSpec] = {}


def register(name: str, *, mode: str, strategy: Optional[str] = None,
             formats: Tuple[str, ...] = ALL_FORMATS,
             supports_stop_after: bool = False,
             supports_batch: bool = False, description: str = ""):
    """Decorator: register an engine under ``name``.  Re-registering a name
    replaces it (supports interactive reloads)."""
    assert mode in ("latency", "throughput"), mode

    def deco(fn):
        _REGISTRY[name] = EngineSpec(
            name=name, fn=fn, mode=mode, strategy=strategy,
            formats=tuple(formats),
            supports_stop_after=supports_stop_after,
            supports_batch=supports_batch, description=description)
        return fn

    return deco


def get_engine(name: str) -> EngineSpec:
    _ensure_builtin()
    if name not in _REGISTRY and name.startswith("resilient:"):
        # engines registered after repro.sort.resilient was imported get
        # their verify-and-repair wrapper built on first request
        inner = name[len("resilient:"):]
        if inner in _REGISTRY:
            from repro.sort.resilient import make_resilient
            return make_resilient(inner)
    if name not in _REGISTRY:
        raise KeyError(f"unknown sort engine {name!r}; "
                       f"available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def available_engines() -> Dict[str, EngineSpec]:
    """name -> spec for every registered engine (built-ins included)."""
    _ensure_builtin()
    return dict(_REGISTRY)


def _ensure_builtin() -> None:
    # built-in engines live in repro.sort.builtin_engines; importing it
    # registers them (deferred to avoid a cycle at package import time).
    # repro.sort.resilient then wraps each of them (and adds "mb-ft").
    import repro.sort.builtin_engines  # noqa: F401
    import repro.sort.resilient  # noqa: F401
