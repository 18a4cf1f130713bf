#!/usr/bin/env python3
"""Bring-up check: the system's main path on one TPU chip, in one process.

    python3 chip_smoke.py [--seed N]

Each phase runs through the entry points a user calls and is checked
against a plain numpy / ``lax`` reference:

  (a) device    platform, ``device_kind``, env stamp, compile-cache directory;
  (b) sort      the ``sort()`` facade at real sizes: ``pallas-tns`` (its
                permutation against numpy, its cycles / DRs / reload cycles
                against the ``tns`` machine), ``tns``, a full ``radix`` sort
                and ``pallas-topk`` at the router shape;
  (c) topk      the in-model dispatchers: ``sort.topk`` on every engine at
                the router shape, ``sort.topk_mask`` at the vocabulary shape;
  (d) serve     the continuous-batching orchestrator answering a trace;
  (e) model     one OLMo-1B prefill + decode at full width with top-k
                sampling over its vocabulary (``repro.launch.serve.serve``).

Every phase prints its compile and run seconds.  The script refuses to run
(exit code != 0, no result line) when JAX finds no TPU, when
``REPRO_PALLAS`` is set, or when the Pallas kernels would not compile; any
mismatch or exception also exits non-zero.  On success the last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# (dtype, B, N, top-m or None for a full sort, LIFO depth k)
TNS_CELLS = (
    ("uint16", 64, 1024, 1, 0), ("uint16", 64, 1024, 1, 2),
    ("uint16", 64, 1024, 2, 0), ("uint16", 64, 1024, 2, 2),
    ("uint16", 16, 4096, 1, 2),
    ("float16", 32, 256, 8, 2),
    ("uint16", 8, 1024, None, 2),
)
ROUTER = (512, 160, 6)          # tokens x experts, top-k
VOCAB = (8, 102_400, 50)        # rows x vocabulary, top-k

# XLA / Mosaic compilation, one event per executable built.  JAX's tracing
# events nest (a jitted callee is traced inside its caller), so summing
# them would count time twice; tracing is counted as run time instead.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration


@contextlib.contextmanager
def timed(label: str):
    """Print the wall seconds of a block, split into backend compile
    seconds (JAX's own compile events, once ``main`` listens) and the
    rest."""
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"[{label}] ok  compile_s={comp:.3f} run_s={wall - comp:.3f} "
          f"wall_s={wall:.3f}", flush=True)


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def make_data(rng: np.random.Generator, dtype: str, shape) -> np.ndarray:
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return rng.standard_normal(shape).astype(dt)
    return rng.integers(0, np.iinfo(dt).max, shape, endpoint=True).astype(dt)


def order_keys(x: np.ndarray) -> np.ndarray:
    """Unsigned keys in the engines' order: unsigned ints as they are,
    IEEE floats by bit pattern (negatives complemented, positives with the
    sign bit set, so -0.0 sorts before +0.0)."""
    if x.dtype.kind == "u":
        return x.astype(np.uint64)
    bits = x.dtype.itemsize * 8
    u = x.view(f"u{x.dtype.itemsize}").astype(np.uint64)
    sign = np.uint64(1 << (bits - 1))
    return np.where(u & sign, np.uint64((1 << bits) - 1) ^ u, u | sign)


def reference_perm(x: np.ndarray, stop_after=None) -> np.ndarray:
    perm = np.argsort(order_keys(x), axis=-1, kind="stable")
    return perm if stop_after is None else perm[..., :stop_after]


def check_engine(x: np.ndarray, engine: str, *, stop_after=None, k=2):
    """``sort()`` on ``engine``; its permutation must equal numpy's."""
    from repro import sort
    res = sort.sort(x, engine=engine, k=k, stop_after=stop_after)
    got = np.asarray(res.indices)
    expect(np.array_equal(got, reference_perm(x, stop_after)),
           f"{engine}: permutation differs from np.argsort "
           f"(shape {x.shape}, stop_after={stop_after})")
    return res


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------


def phase_device(cache_dir: str) -> None:
    import jax
    from repro.kernels import backend
    devices = jax.devices()
    print(f"[device] devices={devices}", flush=True)
    print(f"[device] device_kind={devices[0].device_kind!r} "
          f"count={len(devices)}", flush=True)
    print(f"[device] env={json.dumps(backend.env_stamp(), sort_keys=True)}",
          flush=True)
    print(f"[device] compile_cache={cache_dir}", flush=True)


def phase_sort(rng: np.random.Generator, *, tns_cells=TNS_CELLS,
               machine=(64, 1024, 32), radix=(64, 65_536),
               topk=ROUTER) -> None:
    for dtype, b, n, m, k in tns_cells:
        x = make_data(rng, dtype, (b, n))
        with timed(f"sort pallas-tns {dtype} B={b} N={n} "
                   f"m={m or 'all'} k={k}"):
            fused = check_engine(x, "pallas-tns", stop_after=m, k=k)
            ref = check_engine(x, "tns", stop_after=m, k=k)
            for field in ("cycles", "drs", "reload_cycles"):
                expect(np.array_equal(np.asarray(getattr(fused, field)),
                                      np.asarray(getattr(ref, field))),
                       f"pallas-tns {field} differ from the tns machine "
                       f"({dtype} B={b} N={n} m={m} k={k})")
    b, n, m = machine
    with timed(f"sort tns uint16 B={b} N={n} m={m}"):
        check_engine(make_data(rng, "uint16", (b, n)), "tns", stop_after=m)
    b, n = radix
    with timed(f"sort radix float32 B={b} N={n} full"):
        check_engine(make_data(rng, "float32", (b, n)), "radix")
    b, n, m = topk
    with timed(f"sort pallas-topk float32 B={b} N={n} m={m}"):
        check_engine(make_data(rng, "float32", (b, n)), "pallas-topk",
                     stop_after=m)


def phase_topk(rng: np.random.Generator, *, router=ROUTER,
               vocab=VOCAB) -> None:
    import jax
    import jax.numpy as jnp
    from repro import sort
    b, n, k = router
    x = jnp.asarray(make_data(rng, "float32", (b, n)))
    want_v, want_i = jax.lax.top_k(x, k)
    for engine in sort.TOPK_ENGINES:
        with timed(f"topk {engine} {b}x{n} k={k}"):
            v, i = jax.jit(lambda a, e=engine: sort.topk(a, k, engine=e))(x)
            expect(np.array_equal(np.asarray(v), np.asarray(want_v))
                   and np.array_equal(np.asarray(i), np.asarray(want_i)),
                   f"sort.topk(engine={engine!r}) differs from lax.top_k")
    b, n, k = vocab
    x = jnp.asarray(make_data(rng, "float32", (b, n)))
    want = np.asarray(x >= jax.lax.top_k(x, k)[0][:, -1:])
    with timed(f"topk_mask {b}x{n} k={k}"):
        got = np.asarray(jax.jit(lambda a: sort.topk_mask(a, k))(x))
        expect(np.array_equal(got, want),
               "sort.topk_mask differs from the lax.top_k threshold")


def phase_serve(*, n_requests: int = 16, n: int = 256, chunk: int = 32,
                seed: int = 0) -> None:
    """The orchestrator that ``repro.launch.serve.serve_requests`` builds,
    kept here so every answered request can be checked."""
    from repro import serving
    from repro.serving.request import Status
    trace = serving.make_trace(n_requests, seed=seed, n=n, mean_gap_us=0.1)
    orch = serving.Orchestrator(
        clock=serving.SimulatedClock(),
        cfg=serving.OrchestratorConfig(chunk=chunk))
    with timed(f"serve {n_requests} requests n={n} chunk={chunk}"):
        report = orch.run(trace)
    print(f"[serve] completed={report['completed']} "
          f"accepted={report['accepted']} failed={report['failed']} "
          f"engines={report['engines']}", flush=True)
    expect(report["failed"] == 0, f"{report['failed']} requests FAILED")
    expect(report["completed"] == report["accepted"] == n_requests,
           f"completed {report['completed']} of {report['accepted']} "
           f"accepted ({n_requests} sent)")
    for req in orch.done:
        expect(req.status is Status.DONE and req.ascending,
               f"request {req.rid}: {req.status}")
        expect(np.array_equal(req.indices,
                              reference_perm(req.x, req.target)),
               f"request {req.rid} ({req.engine}): indices differ from "
               "the reference prefix")


def phase_model(cfg, *, batch: int = 4, prompt_len: int = 16,
                max_new: int = 8, top_k: int = 50, seed: int = 0) -> None:
    from repro.launch.serve import serve
    with timed(f"model {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
               f"V={cfg.vocab} batch={batch} prompt={prompt_len} "
               f"new={max_new} top_k={top_k}"):
        res = serve(cfg, batch, prompt_len, max_new, top_k=top_k, seed=seed)
    tokens, logits = res["tokens"], res["logits"]
    print(f"[model] prefill_s={res['prefill_s']:.3f} "
          f"decode_tok_per_s={res['decode_tok_per_s']:.1f} "
          "(both include their first call's compile)", flush=True)
    expect(tokens.shape == (batch, prompt_len + max_new),
           f"tokens shape {tokens.shape}")
    expect(bool(((tokens >= 0) & (tokens < cfg.vocab)).all()),
           "token outside [0, vocab)")
    expect(logits.shape == (batch, cfg.vocab), f"logits {logits.shape}")
    expect(bool(np.isfinite(logits).all()), "non-finite logits")
    # the last token was sampled from these logits under the top-k mask
    kth = np.sort(logits, axis=-1)[:, -top_k]
    expect(bool((logits[np.arange(batch), tokens[:, -1]] >= kth).all()),
           "a sampled token lies outside the top-k logits")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the data, the trace and the weights")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PALLAS"):
        sys.exit(f"chip_smoke: REPRO_PALLAS={os.environ['REPRO_PALLAS']!r} "
                 "is set; it overrides the kernels' compiled mode, so this "
                 "check refuses to run")
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's platform is {device.platform!r}; this "
                 "check runs only on a TPU")
    sys.path.insert(0, str(SRC))
    from repro import configs
    from repro.kernels import backend
    from repro.launch import compile_cache
    if backend.mode() != "compiled":
        sys.exit(f"chip_smoke: Pallas mode is {backend.mode()!r}, "
                 "not 'compiled'")
    cache_dir = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    rng = np.random.default_rng(args.seed)
    phase_device(cache_dir)
    phase_sort(rng)
    phase_topk(rng)
    phase_serve(seed=args.seed)
    phase_model(configs.get_config("olmo_1b"), seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
