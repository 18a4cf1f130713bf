#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
place of the cell's entry, one guarantee broken (see the reference's
``control_call``), driven through the cell's own window and check (an
open loop serves it through ``harness.Synchronous``).  Its
compared numbers must exceed their limits; they are the upper readings the
limits are set below.  The benchmark's own runs never run it.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed: the seed, ``correct`` and the checks.
The control runs on the host, so it needs no chip; on the machine with
the chip it runs at the cell's own size and load.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import measure
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    control = cell.reference.control_call(cell.cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        _, line = measure(cell, seed, args.seconds, False,
                          time.perf_counter(), call=control)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
