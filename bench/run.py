#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n>
                         --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic mix, entry, generator, reference and metric readers are files
under ``bench/`` (``bench/spec.py``).  The run sets up (JAX, the request
pool from the seed, made on a second thread while JAX comes up, each
shape once through the entry), measures for ``--seconds`` in the cell's
closed or open loop (``bench/harness.py``), compares every answer of the
window with the plain reference, and prints one JSON line as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of the window), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each compared number
with its limit.
The same numbers are the last lines of standard error.

It runs only on a TPU, with the Pallas kernels compiled: it exits with a
non-zero code and prints no result where JAX finds no TPU or fewer chips
than the cell asks for, where ``REPRO_PALLAS`` is set, or where the
program's sources are missing.  JAX's compilation cache is the program's
(``repro.launch.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` if set,
otherwise ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def refusal(env, platform: str, n_devices: int, chips: int,
            pallas_mode) -> str | None:
    """Why this process may not measure, or None.  ``pallas_mode`` is
    called only once the rest has passed."""
    if env.get("REPRO_PALLAS"):
        return (f"REPRO_PALLAS={env['REPRO_PALLAS']!r} is set; it overrides "
                "the kernels' compiled mode")
    if platform != "tpu":
        return f"JAX's platform is {platform!r}; the benchmark runs on a TPU"
    if n_devices < chips:
        return f"the cell needs {chips} chips, JAX finds {n_devices}"
    mode = pallas_mode()
    if mode != "compiled":
        return f"Pallas mode is {mode!r}, not 'compiled'"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.spec import load_cell
    from bench.traffic.generate import pool_in_background
    cell = load_cell(args.workload)
    pool = pool_in_background(cell.cfg, cell.traffic, args.seed,
                              cell.generator.make_pool)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    from repro.kernels import backend
    from repro.launch import compile_cache
    why = refusal(os.environ, devices[0].platform, len(devices), cell.chips,
                  backend.mode)
    if why:
        sys.exit(f"bench/run.py: {why}")
    compile_cache.enable()
    from bench.harness import measure
    _, line = measure(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, pool=pool)
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
