"""The generator of request pools for closed-loop traffic, driven by a
traffic mix's data file; a traffic file that names no ``generator`` uses
it.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``loop``: ``"closed"``, each caller waiting for its answer before the
  next call; ``callers``: how many (the harness drives one);
* ``pool``: how many distinct requests set-up makes; the window cycles
  through them in order;
* ``datasets``: datasets of ``datasets.py``, each given to an equal share
  of the pool, all of them given by the paper at the configuration's
  width;
* ``stop_after``: the values of ``stop_after`` (``null`` for a full sort),
  each given to an equal share of every dataset's requests.

Every seed gets the same multiset of (dataset, ``stop_after``) in another
order, with its own data, so a seed changes the numbers sorted and not
the amount of work.  The configuration gives each request's shape
(``batch`` x ``n``), ``width`` and ``dtype``.
"""
from __future__ import annotations

import itertools
import threading
from typing import Callable, NamedTuple

import numpy as np

from bench.traffic.datasets import LAYOUTS, make_dataset


class Request(NamedTuple):
    x: np.ndarray            # (batch, n) keys of the configured dtype
    stop_after: int | None   # None: full sort
    dataset: str


def make_pool(cfg: dict, traffic: dict, seed: int) -> list[Request]:
    given = LAYOUTS.get(cfg["width"], ())
    if not set(traffic["datasets"]) <= set(given):
        raise ValueError(f"the paper gives {list(given)} at {cfg['width']} "
                         f"bits, not {traffic['datasets']}")
    mix = list(itertools.product(traffic["datasets"], traffic["stop_after"]))
    size = traffic["pool"]
    if size % len(mix):
        raise ValueError(f"pool {size} is not a multiple of the {len(mix)} "
                         "(dataset, stop_after) pairs of the mix")
    rng = np.random.default_rng(seed)
    order = rng.permutation(np.repeat(np.arange(len(mix)), size // len(mix)))
    shape, dtype = (cfg["batch"], cfg["n"]), np.dtype(cfg["dtype"])
    pool = []
    for j in order:
        dataset, stop_after = mix[j]
        x = make_dataset(dataset, shape, cfg["width"], rng).astype(dtype)
        pool.append(Request(x, stop_after, dataset))
    return pool


def pool_in_background(cfg: dict, traffic: dict, seed: int,
                       make: Callable = make_pool) -> Callable[[], list]:
    """Start ``make(cfg, traffic, seed)``, the cell's generator, on a
    second thread, so that it overlaps JAX and the chip coming up (numpy's
    generators release the GIL while they fill arrays).  Returns a
    function that waits for the pool."""
    box: dict = {}

    def work():
        try:
            box["pool"] = make(cfg, traffic, seed)
        except BaseException as e:      # re-raised on the caller's thread
            box["error"] = e
    thread = threading.Thread(target=work, name="make_pool", daemon=True)
    thread.start()

    def result() -> list:
        thread.join()
        if "error" in box:
            raise box["error"]
        return box["pool"]
    return result
