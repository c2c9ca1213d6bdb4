"""The bit-packed BiBFS against the Bellman-Ford BiBFS it stands in for.

On a graph whose valid edges all weigh 1, `bounded_bibfs` runs its
waves as BFS level steps over packed frontier words (`frontier_or`);
any other weight sends it through the vmapped Bellman-Ford waves. The
packed path must give the same `(d, waves, live_waves)`, bit for bit, on
every backend and tiling, the autotuner's dst-sorted plans included:
landmark endpoints, `s == t`, unreachable pairs and a binding
`max_steps` cap included. The Bellman-Ford side of each comparison runs
on the same graph plus one isolated edge of weight 2, which no query
reaches and which sends the search down that path.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ref
from repro.core.construct import select_landmarks_by_degree
from repro.core.engine import RelaxEngine, RelaxPlan
from repro.core.query import bounded_bibfs
from repro.graphs import generators as gen
from repro.graphs.coo import (INF_D, OP_REW, apply_batch, from_edges,
                              make_batch, to_numpy_wadj)
from repro.kernels.edge_relax import ops as er_ops

N, ISLE = 40, 6          # main component, and a second one out of reach
BLOCK_V = 16
TILINGS = {"jnp": None, "pallas": None, "pallas_chunked": 8, "sorted": None}


def _plan(g, tiling):
    if tiling == "jnp":
        return None
    if tiling == "sorted":
        return RelaxPlan(tiles=None, backend="pallas", impl="sorted",
                         sorted_tiles=er_ops.prepare_sorted(
                             g.src, g.dst, g.valid, g.n))
    return RelaxEngine(backend="pallas", block_v=BLOCK_V,
                       block_e=TILINGS[tiling]).prepare(g)


def _unit_graph():
    main = gen.random_connected(N, extra_edges=25, seed=5)
    isle = gen.random_connected(ISLE, extra_edges=2, seed=6) + N
    edges = np.concatenate([main, isle]).astype(np.int32)
    return edges, from_edges(N + ISLE, edges, edges.shape[0] + 16)


def _with_weighted_edge(edges, n):
    """The same graph plus an isolated edge (n, n+1) of weight 2."""
    w = np.concatenate([np.c_[edges, np.ones(len(edges), np.int32)],
                        [[n, n + 1, 2]]]).astype(np.int32)
    return from_edges(n + 2, w, w.shape[0] + 16)


def _queries(b, n, landmarks, seed):
    """`b` pairs; the first lanes are s == t, a landmark source, a
    landmark target and a pair across the two components."""
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, b).astype(np.int32)
    t = rng.integers(0, n, b).astype(np.int32)
    lm = [int(x) for x in np.asarray(landmarks)]
    special = [(3, 3), (lm[0], 7), (9, lm[1]), (2, N + 1)]
    for lane, (a, z) in enumerate(special[:b]):
        s[lane], t[lane] = a, z
    return jnp.asarray(s), jnp.asarray(t)


def _oracle(g, landmarks, s, t):
    """d_{G[V\\R]}(s, t) by Dijkstra on the graph less its landmarks."""
    lm = {int(x) for x in np.asarray(landmarks)}
    wadj = {u: {v: w for v, w in nbrs.items() if v not in lm}
            for u, nbrs in to_numpy_wadj(g).items() if u not in lm}
    out = []
    for a, z in zip(np.asarray(s).tolist(), np.asarray(t).tolist()):
        d = (ref.INF if a in lm or z in lm
             else ref.dijkstra_dist(wadj, g.n, a)[z])
        out.append(int(INF_D) if d == ref.INF else int(d))
    return np.asarray(out)


def _run(g, landmarks, s, t, max_steps, plan):
    bound = jnp.full(s.shape, INF_D, jnp.int32)
    d, waves, live, packed = bounded_bibfs(g, landmarks, s, t, bound,
                                           max_steps, plan)
    return np.asarray(d), int(waves), np.asarray(live), bool(packed)


@pytest.mark.parametrize("max_steps", [64, 2], ids=["free", "capped"])
@pytest.mark.parametrize("tiling", list(TILINGS))
@pytest.mark.parametrize("b", [1, 7, 32, 33, 64])
def test_packed_path_matches_bellman_ford(b, tiling, max_steps):
    edges, g = _unit_graph()
    landmarks = select_landmarks_by_degree(g, 3)
    s, t = _queries(b, g.n, landmarks, seed=b)
    gw = _with_weighted_edge(edges, g.n)
    if tiling == "pallas_chunked":
        assert _plan(g, tiling).tiles.chunked
    dp, wp, lp, packed = _run(g, landmarks, s, t, max_steps,
                              _plan(g, tiling))
    db, wb, lb, bf_packed = _run(gw, landmarks, s, t, max_steps,
                                 _plan(gw, tiling))
    assert packed and not bf_packed
    np.testing.assert_array_equal(dp, db)
    assert wp == wb <= max_steps
    np.testing.assert_array_equal(lp, lb)
    if max_steps == 64:
        np.testing.assert_array_equal(dp, _oracle(g, landmarks, s, t))


def _road():
    edges = gen.road_grid(36, max_weight=5, seed=1)
    n = int(edges[:, :2].max()) + 1
    return from_edges(n, edges, edges.shape[0] + 16)


def _ba_reweighted():
    edges = gen.barabasi_albert(48, 3, seed=2)
    g = from_edges(48, edges, edges.shape[0] + 16)
    rew = [(int(u), int(v), OP_REW, 3) for u, v in edges[::5]]
    return apply_batch(g, make_batch(rew))


@pytest.mark.parametrize("tiling", ["jnp", "pallas", "sorted"])
@pytest.mark.parametrize("graph", [_road, _ba_reweighted],
                         ids=["road", "ba_reweighted"])
def test_weighted_graphs_keep_bellman_ford(graph, tiling):
    """Any edge weight other than 1 selects the Bellman-Ford waves, whose
    answers stay exact."""
    g = graph()
    landmarks = select_landmarks_by_degree(g, 3)
    s, t = _queries(16, g.n, landmarks, seed=3)
    t = jnp.where(t >= g.n, 0, t)   # no second component here
    d, waves, _, packed = _run(g, landmarks, s, t, 64, _plan(g, tiling))
    assert not packed and waves <= 64
    np.testing.assert_array_equal(d, _oracle(g, landmarks, s, t))
