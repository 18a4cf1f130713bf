"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run forces
512 host devices while smoke tests must see exactly one.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes the batch shards over (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis(mesh) -> str:
    return "model"


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    return _auto_mesh((n // mp, mp), ("data", "model"))


def _auto_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
