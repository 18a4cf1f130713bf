"""The entry refuses what it may not measure on, and BENCHMARK.json holds
to its contract: every name it gives resolves to a file under bench/."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.run import refusal
from bench.spec import BENCH, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def compiled():
    return "compiled"


@pytest.mark.parametrize("env,platform,n,chips,mode,match", [
    ({"REPRO_PALLAS": "jnp"}, "tpu", 1, 1, compiled, "REPRO_PALLAS"),
    ({}, "cpu", 1, 1, compiled, "platform is 'cpu'"),
    ({}, "tpu", 1, 4, compiled, "needs 4 chips"),
    ({}, "tpu", 1, 1, lambda: "interpret", "Pallas mode"),
])
def test_refusals(env, platform, n, chips, mode, match):
    assert match in refusal(env, platform, n, chips, mode)


def test_a_tpu_with_compiled_kernels_is_accepted():
    assert refusal({}, "tpu", 4, 4, compiled) is None


def run_entry(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "REPRO_PALLAS"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "topm_u8.extract_min",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("env_extra,match", [
    ({}, "platform is 'cpu'"),
    ({"REPRO_PALLAS": "interpret"}, "REPRO_PALLAS"),
])
def test_entry_exits_without_a_result_off_the_chip(env_extra, match):
    p = run_entry(ROOT, env_extra)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert match in p.stderr


def test_entry_needs_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_entry(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    assert all(NAME.match(e["name"]) for e in entries)
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    texts = ([c[k] for c in SPEC["configs"] for k in ("source", "why")]
             + [w["why"] for w in SPEC["workloads"]]
             + [m["layer"] for m in SPEC["per_layer"]])
    assert all(1 <= len(t) <= 200 and not set(t) & set("\t\n") for t in texts)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_name_resolves_to_files_under_bench():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/configs/")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    for w in SPEC["workloads"]:
        cell = load_cell(w["name"])
        assert cell.chips in (1, 4)
        assert {m.name for m in cell.end_to_end} == e2e
        assert cell.per_layer, w["name"]
