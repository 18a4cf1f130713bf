"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas in interpret
mode), its refusal to run anywhere but on a TPU, and where the entry points
put JAX's compilation cache."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import configs
from repro.kernels import backend

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    backend.reset()
    yield
    monkeypatch.delenv("REPRO_PALLAS")
    backend.reset()


PHASES = {
    "device": lambda rng: cs.phase_device("<not enabled>"),
    "sort": lambda rng: cs.phase_sort(
        rng, tns_cells=(("uint16", 4, 200, 1, 0), ("uint16", 4, 200, 2, 2),
                        ("float16", 4, 64, 8, 2), ("uint16", 2, 64, None, 2)),
        machine=(4, 128, 8), radix=(2, 512), topk=(16, 40, 3)),
    "topk": lambda rng: cs.phase_topk(rng, router=(16, 40, 3),
                                      vocab=(2, 2048, 5)),
    "serve": lambda rng: cs.phase_serve(n_requests=5, n=32, chunk=8),
    "model": lambda rng: cs.phase_model(
        configs.get_config("olmo_1b").reduced(n_layers=1, d_model=64,
                                              vocab=256),
        batch=2, prompt_len=4, max_new=2, top_k=5),
}


@pytest.mark.parametrize("phase", list(PHASES))
def test_phase_passes_at_tiny_size(phase, interpret_mode, capsys):
    PHASES[phase](np.random.default_rng(0))
    out = capsys.readouterr().out
    assert "] ok " in out or phase == "device"


def test_reference_orders_floats_by_bit_pattern():
    x = np.array([0.5, -0.0, 0.0, -2.0, np.inf, -np.inf], np.float16)
    assert cs.reference_perm(x).tolist() == [5, 3, 1, 2, 0, 4]


def test_check_engine_rejects_a_wrong_permutation(monkeypatch):
    from repro import sort
    real = sort.sort

    def reversed_sort(x, **kw):
        res = real(x, **kw)
        res.indices = np.asarray(res.indices)[..., ::-1]
        return res

    monkeypatch.setattr(sort, "sort", reversed_sort)
    x = np.arange(8, dtype=np.uint16)
    with pytest.raises(AssertionError, match="permutation differs"):
        cs.check_engine(x, "radix")


def test_main_refuses_the_cpu(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_PALLAS", raising=False)
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_main_refuses_repro_pallas(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PALLAS", "compiled")
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert "REPRO_PALLAS" in str(exc.value.code)
    assert capsys.readouterr().out == ""


_CACHE_PROBE = """
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
"""


def _probe(env_dir, action):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, action],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split("\n")[:2]


def test_compile_cache_honors_env_dir(tmp_path):
    enabled, configured = _probe(tmp_path, "compile")
    assert enabled == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was cached in the env dir"


def test_compile_cache_defaults_to_fixed_repo_dir():
    enabled, configured = _probe(None, "none")
    assert enabled == configured == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
