"""Property tests for the paper's core: BatchHL vs a from-scratch oracle.

Invariants under random graphs × random batches (hypothesis-driven):
  * construction reproduces the oracle's minimal highway-cover labelling
    (Theorem in [17]; distances, hub flags, label masks, highway),
  * BatchHL (both BHL and BHL+) maintains exactly the minimal labelling of
    G' (Theorem 5.21: correctness + minimality),
  * batch search supersets: improved ⊇ LD-affected (Lemma 5.18),
    basic ⊇ affected (Lemma 5.8), and |improved| ≤ |basic| (Table 5),
  * queries are exact (paper §4),
  * no-op batches and insert+delete round-trips leave the labelling fixed.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests only; optional dep
pytestmark = pytest.mark.slow  # property tests: full CI job only
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.graphs import generators as gen
from repro.graphs.coo import from_edges, make_batch, to_numpy_adj, INF_D
from repro.core.construct import build_labelling
from repro.core.batch import (batchhl_update, batchhl_update_split,
                              batch_search_basic, batch_search_improved,
                              uhl_update)
from repro.core.query import batched_query
from repro.core import ref

SETTINGS = dict(deadline=None, max_examples=20,
                suppress_health_check=[HealthCheck.too_slow,
                                       HealthCheck.data_too_large])


def _setup(seed: int, n: int, n_land: int):
    edges = gen.random_connected(n, extra_edges=n // 2, seed=seed)
    g = from_edges(n, edges, edges.shape[0] + 64)
    deg = np.zeros(n)
    for u, v in edges:
        deg[u] += 1
        deg[v] += 1
    landmarks = np.argsort(-deg, kind="stable")[:n_land].astype(np.int32)
    lab = build_labelling(g, jnp.asarray(landmarks))
    return edges, g, landmarks, lab


def _oracle_labelling(adj, n, landmarks):
    return ref.minimal_labelling(adj, n, list(landmarks))


def _assert_matches_oracle(lab, adj, n, landmarks):
    od, oh, ohw, omask = _oracle_labelling(adj, n, landmarks)
    jd = np.asarray(lab.dist)
    jh = np.asarray(lab.hub)
    jm = np.asarray(lab.label_mask())
    jhw = np.asarray(lab.highway)
    for i in range(len(landmarks)):
        for v in range(n):
            want = od[i][v] if od[i][v] != ref.INF else int(INF_D)
            assert jd[i, v] == want, (i, v, jd[i, v], want)
            if od[i][v] != ref.INF:
                assert bool(jh[i, v]) == oh[i][v], (i, v)
            assert bool(jm[i, v]) == omask[i][v], (i, v)
        for j in range(len(landmarks)):
            want = ohw[i][j] if ohw[i][j] != ref.INF else int(INF_D)
            assert jhw[i, j] == want


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 48),
       n_land=st.integers(1, 5))
def test_construction_matches_oracle(seed, n, n_land):
    edges, g, landmarks, lab = _setup(seed, n, min(n_land, n))
    _assert_matches_oracle(lab, to_numpy_adj(g), n, landmarks)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(10, 40),
       n_ins=st.integers(0, 5), n_del=st.integers(0, 5),
       improved=st.booleans())
def test_batch_update_maintains_minimal_labelling(seed, n, n_ins, n_del,
                                                  improved):
    edges, g, landmarks, lab = _setup(seed, n, 3)
    ups = gen.random_batch_updates(edges, n, n_ins=n_ins, n_del=n_del,
                                   seed=seed + 1)
    batch = make_batch(ups, pad_to=max(n_ins + n_del, 1))
    g2, lab2, _ = batchhl_update(g, batch, lab, improved=improved)
    adj2 = ref.apply_updates(to_numpy_adj(g), ups)
    # graph update itself is correct
    assert to_numpy_adj(g2) == adj2
    # labelling is the minimal labelling of G' (Thm 5.21)
    _assert_matches_oracle(lab2, adj2, n, landmarks)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(10, 36))
def test_affected_supersets_and_pruning(seed, n):
    edges, g, landmarks, lab = _setup(seed, n, 3)
    ups = gen.random_batch_updates(edges, n, n_ins=3, n_del=3, seed=seed + 1)
    batch = make_batch(ups, pad_to=6)
    from repro.graphs.coo import apply_batch
    g2 = apply_batch(g, batch)
    adj, adj2 = to_numpy_adj(g), to_numpy_adj(g2)

    aff_b = np.asarray(batch_search_basic(g, g2, batch, lab))
    aff_i = np.asarray(batch_search_improved(g, g2, batch, lab))
    for i, r in enumerate(landmarks):
        full = ref.affected_set(adj, adj2, n, int(r))
        ld = ref.ld_affected_set(adj, adj2, n, list(landmarks), int(r))
        assert all(aff_b[i, v] for v in full), "Lemma 5.8 violated"
        assert all(aff_i[i, v] for v in ld), "Lemma 5.18 violated"
        # improved search prunes at least as hard as basic (Table 5)
        assert aff_i[i].sum() <= aff_b[i].sum()


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(10, 36))
def test_queries_exact_after_update(seed, n):
    edges, g, landmarks, lab = _setup(seed, n, 3)
    ups = gen.random_batch_updates(edges, n, n_ins=2, n_del=3, seed=seed + 9)
    batch = make_batch(ups, pad_to=5)
    g2, lab2, _ = batchhl_update(g, batch, lab, improved=True)
    adj2 = to_numpy_adj(g2)
    rng = np.random.default_rng(seed)
    qs = rng.integers(0, n, 16).astype(np.int32)
    qt = rng.integers(0, n, 16).astype(np.int32)
    got = np.asarray(batched_query(g2, lab2, jnp.asarray(qs),
                                   jnp.asarray(qt)))
    for k in range(16):
        want = ref.pair_distance(adj2, n, int(qs[k]), int(qt[k]))
        want = 0 if qs[k] == qt[k] else want
        want = int(INF_D) if want == ref.INF else want
        assert got[k] == want, (qs[k], qt[k], got[k], want)


def test_noop_batch_is_identity():
    edges, g, landmarks, lab = _setup(3, 24, 3)
    batch = make_batch([(0, 1, False)], pad_to=4)
    batch = batch.__class__(batch.src, batch.dst, batch.is_del,
                            jnp.zeros_like(batch.valid),  # all padding
                            batch.w, batch.is_rew)
    g2, lab2, aff = batchhl_update(g, batch, lab)
    assert not bool(jnp.any(aff))
    assert bool(jnp.all(lab2.dist == lab.dist))
    assert bool(jnp.all(lab2.hub == lab.hub))


def test_insert_then_delete_roundtrip():
    edges, g, landmarks, lab = _setup(5, 24, 3)
    ups = gen.random_batch_updates(edges, 24, n_ins=3, n_del=0, seed=11)
    batch = make_batch(ups, pad_to=3)
    g2, lab2, _ = batchhl_update(g, batch, lab)
    rev = make_batch([(u, v, True) for (u, v, _) in ups], pad_to=3)
    g3, lab3, _ = batchhl_update(g2, rev, lab2)
    assert bool(jnp.all(lab3.dist == lab.dist))
    assert bool(jnp.all(lab3.hub == lab.hub))
    assert bool(jnp.all(lab3.highway == lab.highway))


def test_split_and_unit_variants_agree():
    """BHL, BHL^s and UHL+ must all land on the same minimal labelling."""
    edges, g, landmarks, lab = _setup(7, 28, 3)
    ups = gen.random_batch_updates(edges, 28, n_ins=3, n_del=3, seed=13)
    batch = make_batch(ups, pad_to=6)
    _, lab_b, _ = batchhl_update(g, batch, lab, improved=True)
    _, lab_s, _ = batchhl_update_split(g, batch, lab, improved=True)
    _, lab_u, _ = uhl_update(g, batch, lab, improved=True)
    for a, b in ((lab_b, lab_s), (lab_b, lab_u)):
        assert bool(jnp.all(a.dist == b.dist))
        assert bool(jnp.all(a.hub == b.hub))


def test_disconnection_and_reconnection():
    """Deleting a bridge makes distances INF; reinserting restores them."""
    # path graph 0-1-2-3 with landmark 0
    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int32)
    g = from_edges(4, edges, 8)
    lab = build_labelling(g, jnp.asarray([0], jnp.int32))
    batch = make_batch([(1, 2, True)], pad_to=1)
    g2, lab2, _ = batchhl_update(g, batch, lab)
    assert int(lab2.dist[0, 2]) == int(INF_D)
    assert int(lab2.dist[0, 3]) == int(INF_D)
    back = make_batch([(1, 2, False)], pad_to=1)
    g3, lab3, _ = batchhl_update(g2, back, lab2)
    assert int(lab3.dist[0, 2]) == 2
    assert int(lab3.dist[0, 3]) == 3
