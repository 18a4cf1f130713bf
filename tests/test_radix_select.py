"""Throughput-mode comparison-free selection vs. lax references."""
import functools

import numpy as np
import pytest
from _prop import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import bitplane as bp
from repro.core import radix_select as rs

_f32 = st.floats(-1e6, 1e6, allow_nan=False, width=32)


class TestExactTopK:
    @given(st.lists(_f32, min_size=16, max_size=16), st.sampled_from([1, 4, 6]))
    @settings(max_examples=25, deadline=None)
    def test_matches_lax_topk(self, data, k):
        x = jnp.asarray(np.array(data, dtype=np.float32))[None, :]
        v, i = rs.topk_values(x, k)
        vr, ir = jax.lax.top_k(x, k)
        np.testing.assert_allclose(np.asarray(v), np.asarray(vr))

    def test_bf16_router_shapes(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 7, 160)), dtype=jnp.bfloat16)
        v, i = rs.topk_values(x, 6)
        vr, ir = jax.lax.top_k(x.astype(jnp.float32), 6)
        np.testing.assert_allclose(np.asarray(v, dtype=np.float32),
                                   np.asarray(vr))

    def test_tie_handling_first_index(self):
        x = jnp.asarray(np.array([[1.0, 5.0, 5.0, 0.0]], np.float32))
        _, i = rs.topk_values(x, 2)
        np.testing.assert_array_equal(np.asarray(i)[0], [1, 2])


class TestThresholdMask:
    @given(st.lists(st.integers(-1000, 1000), min_size=32, max_size=32),
           st.integers(1, 31))
    @settings(max_examples=25, deadline=None)
    def test_mask_selects_k_smallest(self, data, k):
        x = jnp.asarray(np.array(data, dtype=np.float32))
        keys = bp.sort_key_jnp(x)
        m = np.asarray(rs.topk_threshold_mask(keys, k))
        assert m.sum() == k
        chosen = np.sort(np.array(data, np.float32)[m])
        ref = np.sort(np.array(data, np.float32))[:k]
        np.testing.assert_allclose(chosen, ref)

    def test_traced_k_runtime_tunable(self):
        # run-time tunable sparsity: k is a traced value, one compilation
        x = jnp.asarray(np.random.default_rng(1).standard_normal(64),
                        dtype=jnp.float32)
        f = jax.jit(lambda xs, kk: rs.prune_smallest_mask(xs, kk))
        for k in [3, 17, 40]:
            m = np.asarray(f(x, jnp.int32(k)))
            assert m.sum() == k

    def test_logits_mask_top1_is_argmax(self):
        x = jnp.asarray(np.random.default_rng(2).standard_normal((5, 100)),
                        dtype=jnp.float32)
        m = np.asarray(rs.topk_logits_mask(x, 1))
        np.testing.assert_array_equal(m.argmax(-1), np.asarray(x).argmax(-1))


class TestRadixSort:
    @given(st.lists(_f32, min_size=2, max_size=48))
    @settings(max_examples=25, deadline=None)
    def test_sorts_floats(self, data):
        x = jnp.asarray(np.array(data, dtype=np.float32))
        sv, perm = rs.sort_values(x)
        np.testing.assert_allclose(np.asarray(sv), np.sort(data))
        assert len(set(np.asarray(perm).tolist())) == len(data)

    def test_stability(self):
        x = jnp.asarray(np.array([3, 1, 2, 1, 3, 1], np.int32))
        _, p = rs.sort_values(x)
        np.testing.assert_array_equal(np.asarray(p), [1, 3, 5, 2, 0, 4])

    @given(st.lists(st.integers(0, 2**31 - 1), min_size=2, max_size=32))
    @settings(max_examples=15, deadline=None)
    def test_uint_keys_r8(self, data):
        keys = jnp.asarray(np.array(data, dtype=np.uint32))
        perm = rs.radix_sort_keys(keys, r=8)
        out = np.asarray(keys)[np.asarray(perm)]
        np.testing.assert_array_equal(out, np.sort(np.array(data, np.uint32)))


# ---------------------------------------------------------------------------
# radix_sort_keys on both sides of the dense bound: the dense one-hot
# passes (rows of at most DENSE_MAX_N keys) and the gather passes.
# ---------------------------------------------------------------------------

BOUND = rs.DENSE_MAX_N
_DT_R = [(np.uint8, 4), (np.uint8, 8), (np.uint16, 4), (np.uint16, 8),
         (np.uint32, 4), (np.uint32, 8)]
_LEADS = [(), (3,), (2, 3)]


def _keys(dtype, shape, kind, seed=0):
    rng = np.random.default_rng(seed)
    top = int(np.iinfo(dtype).max)
    if kind == "random":
        return rng.integers(0, top, shape, endpoint=True,
                            dtype=np.uint64).astype(dtype)
    if kind == "equal":
        return np.full(shape, 5, dtype)
    # extremal: only 0 and the largest key, many ties of each
    return np.where(rng.random(shape) < 0.5, 0, top).astype(dtype)


def _expect(keys, descending):
    ref = np.argsort(keys, axis=-1, kind="stable")
    return np.flip(ref, axis=-1) if descending else ref


_PARITY = ([(dt, r, n, _LEADS[i % 3], i % 2 == 1, "random")
            for i, (n, (dt, r)) in enumerate(
                (n, dr) for n in (1, 2, 127, 1000, 1024) for dr in _DT_R)]
           + [(np.uint32, 8, n, lead, desc, "random")
              for n, lead, desc in ((BOUND, (), False), (BOUND, (3,), True),
                                    (BOUND + 1, (), True),
                                    (BOUND + 1, (2, 3), False))]
           + [(dt, r, n, (), desc, kind)
              for kind, dt, r in (("equal", np.uint16, 8),
                                  ("extremal", np.uint32, 8))
              for n in (1000, BOUND + 1) for desc in (False, True)])


@pytest.mark.parametrize(
    "dtype,r,n,lead,descending,kind", _PARITY,
    ids=[f"{np.dtype(c[0]).name}-r{c[1]}-n{c[2]}-lead{len(c[3])}-"
         f"{'desc' if c[4] else 'asc'}-{c[5]}" for c in _PARITY])
def test_radix_sort_keys_matches_stable_argsort(dtype, r, n, lead,
                                                descending, kind):
    keys = _keys(dtype, lead + (n,), kind, seed=n)
    got = rs.radix_sort_keys(jnp.asarray(keys), r=r, descending=descending)
    assert got.shape == keys.shape and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), _expect(keys, descending))


def test_dense_and_gather_passes_give_the_same_permutation(monkeypatch):
    keys = _keys(np.uint32, (4, 1000), "random", seed=9) % 50  # ties
    dense = rs.radix_sort_keys(jnp.asarray(keys), r=8)
    monkeypatch.setattr(rs, "DENSE_MAX_N", 0)
    gathers = jax.jit(rs.radix_sort_keys.__wrapped__,
                      static_argnames=("r", "descending"))(
        jnp.asarray(keys), r=8)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(gathers))


def _primitives(n):
    jaxpr = jax.make_jaxpr(functools.partial(rs.radix_sort_keys, r=8))(
        jax.ShapeDtypeStruct((2, n), jnp.uint32))
    seen = set()

    def walk(j):
        for eqn in j.eqns:
            seen.add(eqn.primitive.name)
            for v in eqn.params.values():       # the jit's inner jaxpr
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(jaxpr.jaxpr)
    return seen


@pytest.mark.parametrize("n", [1024, BOUND, BOUND + 1])
def test_no_comparison_sort_and_no_gathers_on_the_dense_path(n):
    prims = _primitives(n)
    assert not prims & {"sort", "top_k", "argsort"}, prims
    moves = prims & {"gather", "scatter"}
    assert (not moves) if rs.takes_dense_path(n) else moves, prims
