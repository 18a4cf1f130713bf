"""The paper's benchmark sorting datasets (arXiv:2309.10350 §5.4).

Copied from ``benchmarks/datasets.py`` so that the benchmark's yardstick
cannot move with the program.  The only change: ``make_dataset`` fills a
whole ``shape`` from a caller's generator, so a (B, N) request is one call
and every array of it is drawn from the seed.

The paper gives two key layouts: 8-bit keys with three datasets and
32-bit keys with five (``LAYOUTS``).  random / normal / clustered are
specified exactly; Kruskal's and MapReduce are the classical workloads
(MST edge weights; word-count key frequencies) quantized to W-bit unsigned
fixed point.
"""
from __future__ import annotations

import numpy as np

DATASETS = ("random", "normal", "clustered", "kruskal", "mapreduce")
LAYOUTS = {8: DATASETS[:3], 32: DATASETS}


def make_dataset(name: str, shape, width: int,
                 rng: np.random.Generator) -> np.ndarray:
    """W-bit unsigned keys of the named dataset, as uint64."""
    hi = 2 ** width
    if name == "random":
        return rng.integers(0, hi, shape, dtype=np.uint64)
    if name == "normal":
        mean, std = 2 ** (width - 1), 2 ** (width - 1) / 3
        v = rng.normal(mean, std, shape)
        return np.clip(v, 0, hi - 1).astype(np.uint64)
    if name == "clustered":
        if width == 8:
            centers, std = np.array([100, 200]), 10
        else:
            centers, std = np.array([2 ** 15, 2 ** 25]), 2 ** 13
        c = rng.integers(0, len(centers), shape)
        v = rng.normal(centers[c], std)
        return np.clip(v, 0, hi - 1).astype(np.uint64)
    if name == "kruskal":
        # MST workload: euclidean edge weights of random points — smooth,
        # heavily mid-range concentrated, many near-duplicates
        pts = rng.random(tuple(shape) + (2,))
        other = rng.random(tuple(shape) + (2,))
        d = np.sqrt(((pts - other) ** 2).sum(-1)) / np.sqrt(2)
        return (d * (hi - 1)).astype(np.uint64)
    if name == "mapreduce":
        # word-count key frequencies: zipf-skewed with massive duplication
        v = rng.zipf(1.3, shape).astype(np.float64)
        return np.minimum(v, hi - 1).astype(np.uint64)
    raise ValueError(f"unknown dataset {name!r}; expected one of {DATASETS}")
