"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests only; optional dep
pytestmark = pytest.mark.slow  # property tests: full CI job only
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.kernels.minplus import kernel as mpk, ref as mpr
from repro.kernels.edge_relax import kernel as erk, ops as ero, ref as err
from repro.kernels.embed_bag import kernel as ebk, ref as ebr

INF = 1 << 29
SETTINGS = dict(deadline=None, max_examples=15,
                suppress_health_check=[HealthCheck.too_slow])


# --- minplus ---------------------------------------------------------------

@pytest.mark.parametrize("b,r", [(1, 1), (7, 3), (64, 20), (300, 33),
                                 (257, 128), (512, 129)])
def test_minplus_shapes(b, r):
    rng = np.random.default_rng(b * 1000 + r)
    s = rng.integers(0, 100, (b, r)).astype(np.int32)
    h = rng.integers(0, 100, (r, r)).astype(np.int32)
    t = rng.integers(0, 100, (b, r)).astype(np.int32)
    s[rng.random((b, r)) < 0.3] = INF
    t[rng.random((b, r)) < 0.3] = INF
    got = mpk.minplus_pallas(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t),
                             interpret=True)
    want = mpr.minplus_bound(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), b=st.integers(1, 80),
       r=st.integers(1, 40))
def test_minplus_property(seed, b, r):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, 1 << 20, (b, r)).astype(np.int32)
    h = rng.integers(0, 1 << 20, (r, r)).astype(np.int32)
    t = rng.integers(0, 1 << 20, (b, r)).astype(np.int32)
    got = mpk.minplus_pallas(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t),
                             interpret=True)
    want = mpr.minplus_bound(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- edge_relax ------------------------------------------------------------

@pytest.mark.parametrize("n,e,bv", [(16, 40, 8), (300, 1200, 64),
                                    (1000, 5000, 128), (77, 200, 32)])
def test_edge_relax_shapes(n, e, bv):
    rng = np.random.default_rng(n + e)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    valid = rng.random(e) < 0.8
    keys = rng.integers(0, 1 << 20, n).astype(np.int32)
    bg = ero.prepare(src, dst, valid, n, block_v=bv)
    got = erk.edge_relax_pallas(jnp.asarray(keys), bg.src_t, bg.dstloc_t,
                                bg.valid_t, 2, bg.n, bg.block_v,
                                interpret=True,
                                rowblk_t=bg.rowblk_t if bg.chunked else None,
                                nb=bg.nb)
    want = err.edge_relax(jnp.asarray(keys), jnp.asarray(src),
                          jnp.asarray(dst), jnp.asarray(valid), 2, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(4, 200),
       e=st.integers(1, 600))
def test_edge_relax_property(seed, n, e):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    valid = rng.random(e) < 0.7
    keys = rng.integers(0, 1 << 20, n).astype(np.int32)
    bg = ero.prepare(src, dst, valid, n, block_v=32)
    got = ero.edge_relax(jnp.asarray(keys), bg, 2, use_pallas=True)
    want = err.edge_relax(jnp.asarray(keys), jnp.asarray(src),
                          jnp.asarray(dst), jnp.asarray(valid), 2, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- embed_bag -------------------------------------------------------------

@pytest.mark.parametrize("n,d,b,l", [(100, 8, 16, 3), (500, 64, 100, 7),
                                     (50, 128, 130, 20), (1000, 32, 64, 50)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_embed_bag_shapes(n, d, b, l, dtype):
    rng = np.random.default_rng(n + d)
    table = rng.normal(size=(n, d)).astype(dtype)
    idx = rng.integers(0, n, (b, l)).astype(np.int32)
    w = rng.random((b, l)).astype(np.float32)
    got = ebk.embed_bag_pallas(jnp.asarray(table), jnp.asarray(idx),
                               jnp.asarray(w), interpret=True)
    want = ebr.embed_bag(jnp.asarray(table), jnp.asarray(idx),
                         jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 300),
       d=st.integers(1, 64), b=st.integers(1, 60), l=st.integers(1, 16))
def test_embed_bag_property(seed, n, d, b, l):
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(n, d)).astype(np.float32)
    idx = rng.integers(0, n, (b, l)).astype(np.int32)
    w = rng.random((b, l)).astype(np.float32)
    got = ebk.embed_bag_pallas(jnp.asarray(table), jnp.asarray(idx),
                               jnp.asarray(w), interpret=True)
    want = ebr.embed_bag(jnp.asarray(table), jnp.asarray(idx),
                         jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_embed_bag_masked_mean():
    from repro.kernels.embed_bag import ops as ebo
    rng = np.random.default_rng(0)
    table = rng.normal(size=(50, 8)).astype(np.float32)
    idx = rng.integers(0, 50, (10, 5)).astype(np.int32)
    mask = rng.random((10, 5)) < 0.6
    got = ebo.embed_bag(jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(mask), mode="mean", use_pallas=True)
    # manual oracle
    want = np.zeros((10, 8), np.float32)
    for b in range(10):
        rows = [table[idx[b, j]] for j in range(5) if mask[b, j]]
        if rows:
            want[b] = np.mean(rows, axis=0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
