"""Backend-aware dispatch for the Pallas kernels.

Every Pallas call site used to hardcode ``interpret=True`` (correct on the
CPU-only container, wrong on a real TPU where the kernels should compile).
This module centralizes the decision:

* ``mode()`` returns one of

  - ``"compiled"``  — real Pallas lowering (TPU/GPU backends),
  - ``"interpret"`` — Pallas interpret mode (CPU: kernel bodies execute as
    Python for correctness validation),
  - ``"jnp"``       — pure-jnp oracle fallback (:mod:`repro.kernels.ref`)
    for environments where Pallas itself is unusable;

* ``use_interpret()`` collapses that to the boolean ``pallas_call`` wants.

Resolution order: the ``REPRO_PALLAS`` environment variable
(``compiled`` / ``interpret`` / ``jnp``) wins; otherwise the default JAX
backend picks (``tpu``/``gpu`` -> compiled, ``cpu`` -> interpret).  A
backend that fails to start raises; it never turns into interpret mode.
The result is cached — backends don't change mid-process — but
:func:`reset` clears the cache for tests.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_VALID = ("compiled", "interpret", "jnp")
_cached_mode: Optional[str] = None


def mode() -> str:
    """The dispatch mode for Pallas kernels in this process."""
    global _cached_mode
    if _cached_mode is None:
        env = os.environ.get("REPRO_PALLAS", "").strip().lower()
        if env:
            if env not in _VALID:
                raise ValueError(
                    f"REPRO_PALLAS={env!r}; expected one of {_VALID}")
            _cached_mode = env
        else:
            # no try/except: a process that cannot start its backend (a
            # chip another process holds, say) must fail here, not
            # quietly run every kernel in interpret mode
            _cached_mode = ("compiled"
                            if jax.default_backend() in ("tpu", "gpu")
                            else "interpret")
    return _cached_mode


def use_interpret(interpret: Optional[bool] = None) -> bool:
    """Boolean for ``pallas_call(interpret=...)``.  An explicit caller
    choice wins; otherwise the resolved mode decides (the ``jnp`` mode
    never reaches a ``pallas_call`` — wrappers divert to the oracle first,
    but if one slips through, interpret is the safe answer)."""
    if interpret is not None:
        return interpret
    return mode() != "compiled"


def use_ref(interpret: Optional[bool] = None) -> bool:
    """True when wrappers should route to the pure-jnp oracles instead of
    any ``pallas_call`` (explicit interpret choice opts out)."""
    return interpret is None and mode() == "jnp"


def env_stamp() -> dict:
    """Provenance stamp for a run's output: which backend, chip, device
    count, JAX version and Pallas dispatch mode produced the numbers."""
    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax_version": jax.__version__,
        "pallas_mode": mode(),
    }


def reset() -> None:
    """Forget the cached mode (tests poke REPRO_PALLAS).

    Takes effect for calls routed through :mod:`repro.kernels.ops`, which
    resolve ``interpret`` to a concrete bool before entering jit (so the
    mode is part of the jit cache key).  Calling the kernel modules
    directly with ``interpret=None`` resolves INSIDE the jitted function:
    shapes already traced under the old mode keep their cached
    executable."""
    global _cached_mode
    _cached_mode = None
