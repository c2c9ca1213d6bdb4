"""Directed-graph BatchHL (paper §6): both labelling planes vs the directed
oracle, batch updates, and exact directed queries."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")  # property tests only; optional dep
pytestmark = pytest.mark.slow  # property tests: full CI job only
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.graphs.coo import make_batch, INF_D
from repro.core import ref
from repro.core.directed import (from_arcs, apply_batch_directed,
                                 build_directed_labelling,
                                 batchhl_update_directed, directed_query)

SETTINGS = dict(deadline=None, max_examples=15,
                suppress_health_check=[HealthCheck.too_slow])


def _random_digraph(rng, n):
    m = max(n, int(rng.integers(n, 3 * n)))
    arcs = set()
    # weakly-connected backbone
    for v in range(1, n):
        u = int(rng.integers(v))
        arcs.add((u, v) if rng.random() < 0.7 else (v, u))
    while len(arcs) < m:
        u, v = int(rng.integers(n)), int(rng.integers(n))
        if u != v:
            arcs.add((u, v))
    return np.asarray(sorted(arcs), np.int32)


def _adj_out(g):
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    valid = np.asarray(g.valid)
    adj = {v: set() for v in range(g.n)}
    for s, d, ok in zip(src, dst, valid):
        if ok:
            adj[int(s)].add(int(d))
    return adj


def _landmarks(arcs, n, k):
    deg = np.zeros(n)
    for u, v in arcs:
        deg[u] += 1
        deg[v] += 1
    return np.argsort(-deg, kind="stable")[:k].astype(np.int32)


def _check_plane(lab_plane, adj_out, n, landmarks):
    od, oh, ohw, omask = ref.minimal_labelling_directed(
        adj_out, n, list(landmarks))
    jd = np.asarray(lab_plane.dist)
    jh = np.asarray(lab_plane.hub)
    jm = np.asarray(lab_plane.label_mask())
    for i in range(len(landmarks)):
        for v in range(n):
            want = od[i][v] if od[i][v] != ref.INF else int(INF_D)
            assert jd[i, v] == want, (i, v, jd[i, v], want)
            if od[i][v] != ref.INF:
                assert bool(jh[i, v]) == oh[i][v], (i, v)
            assert bool(jm[i, v]) == omask[i][v], (i, v)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 32))
def test_directed_construction_matches_oracle(seed, n):
    rng = np.random.default_rng(seed)
    arcs = _random_digraph(rng, n)
    g = from_arcs(n, arcs, arcs.shape[0] + 16)
    landmarks = _landmarks(arcs, n, 3)
    lab = build_directed_labelling(g, jnp.asarray(landmarks))
    adj_out = _adj_out(g)
    _check_plane(lab.fwd, adj_out, n, landmarks)
    _check_plane(lab.bwd, ref.reverse_adj(adj_out, n), n, landmarks)


@settings(**SETTINGS)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 28),
       n_ins=st.integers(0, 4), n_del=st.integers(0, 4))
def test_directed_batch_update_and_queries(seed, n, n_ins, n_del):
    rng = np.random.default_rng(seed)
    arcs = _random_digraph(rng, n)
    g = from_arcs(n, arcs, arcs.shape[0] + 2 * (n_ins + 1))
    landmarks = _landmarks(arcs, n, 3)
    lab = build_directed_labelling(g, jnp.asarray(landmarks))

    existing = {(int(u), int(v)) for u, v in arcs}
    ups = []
    if n_del:
        picks = rng.choice(len(arcs), size=min(n_del, len(arcs)),
                           replace=False)
        ups += [(int(arcs[i, 0]), int(arcs[i, 1]), True) for i in picks]
    tries = 0
    while sum(1 for x in ups if not x[2]) < n_ins and tries < 200:
        tries += 1
        u, v = int(rng.integers(n)), int(rng.integers(n))
        if u != v and (u, v) not in existing:
            existing.add((u, v))
            ups.append((u, v, False))
    batch = make_batch(ups, pad_to=max(len(ups), 1))
    if not ups:
        batch = make_batch([(0, 1, False)], pad_to=1)
        batch = batch.__class__(batch.src, batch.dst, batch.is_del,
                                jnp.zeros_like(batch.valid), batch.w,
                                batch.is_rew)

    g2, lab2, _ = batchhl_update_directed(g, batch, lab)
    adj2 = ref.apply_updates_directed(_adj_out(g), ups)
    assert _adj_out(g2) == adj2
    _check_plane(lab2.fwd, adj2, n, landmarks)
    _check_plane(lab2.bwd, ref.reverse_adj(adj2, n), n, landmarks)

    qs = rng.integers(0, n, 12).astype(np.int32)
    qt = rng.integers(0, n, 12).astype(np.int32)
    got = np.asarray(directed_query(g2, lab2, jnp.asarray(qs),
                                    jnp.asarray(qt)))
    for k in range(12):
        want = ref.bfs_dist_directed(adj2, n, int(qs[k]))[int(qt[k])]
        want = 0 if qs[k] == qt[k] else want
        want = int(INF_D) if want == ref.INF else want
        assert got[k] == want, (qs[k], qt[k], got[k], want)
