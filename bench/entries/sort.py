"""The closed loop's entry: ``repro.sort.sort`` as a user calls it, with
the configuration's engine, format, width, LIFO depth and direction."""
from __future__ import annotations

import numpy as np


def make(cfg: dict):
    """``call(x, stop_after)``: one synchronous ``sort()`` of a (B, N)
    batch, returning its result with host arrays."""
    from repro import sort
    kw = dict(engine=cfg["engine"], fmt=cfg["fmt"], width=cfg["width"],
              k=cfg["k"], ascending=cfg["ascending"])

    def call(x: np.ndarray, stop_after: int | None):
        return sort.sort(x, stop_after=stop_after, **kw)
    return call
