"""Find one cell of ``BENCHMARK.json`` and everything that belongs to it by
name.

A cell (a ``workloads`` entry) names a configuration and a traffic mix.
Their data files name the rest:

* the configuration's file (``configs[].file``) names its ``entry``, the
  system under test: ``bench/entries/<entry>.py``, ``sort`` where it
  names none; and its ``reference``: ``bench/references/<reference>.py``;
* the traffic file, ``bench/traffic/<traffic>.json``, names its
  ``generator``, the module of ``bench/traffic/`` whose ``make_pool``
  makes the requests (``generate`` where it names none), and its
  ``loop``, ``closed`` or ``open`` (``bench/harness.py`` says what each
  times);
* each metric the cell reports is read by ``bench/metrics/<name>.py``.

Nothing here names a cell, a configuration, a traffic mix, an entry, a
generator or a metric.  A later cell adds files and entries; this module
does not change.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Metric(NamedTuple):
    name: str
    unit: str
    required: bool      # listed for this cell by name: it must read
    reader: ModuleType  # bench/metrics/<name>.py, with read(run)


class Cell(NamedTuple):
    name: str
    chips: int
    cfg: dict
    traffic: dict
    reference: ModuleType
    end_to_end: list[Metric]
    per_layer: list[Metric]
    entry: ModuleType       # bench/entries/<entry>.py, with make(cfg)
    generator: ModuleType   # bench/traffic/<generator>.py, with make_pool


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric files carry dots in their names).
    Entries, generators, references and readers are all found so."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metrics(entries: list[dict], cell: str) -> list[Metric]:
    out = []
    for m in entries:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        out.append(Metric(m["name"], m["unit"], cells is not None,
                          load_module(BENCH / "metrics" / f"{m['name']}.py")))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``: its data files read
    under ``root``, the code they name (entry, generator, reference,
    metric readers) loaded by path from this ``bench/``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    reference = load_module(BENCH / "references" / f"{cfg['reference']}.py")
    entry = load_module(BENCH / "entries" / f"{cfg.get('entry', 'sort')}.py")
    generator = load_module(
        BENCH / "traffic" / f"{traffic.get('generator', 'generate')}.py")
    return Cell(name, w["chips"], cfg, traffic, reference,
                _metrics(bench["end_to_end"], name),
                _metrics(bench["per_layer"], name), entry, generator)
