"""Relaxation-engine dispatch parity: jnp and Pallas backends must be
bit-identical on every sweep shape the system uses (DESIGN.md §3).

Deterministic (no hypothesis dependency — this file is the bare-checkout
coverage for the hot paths): random graphs across small V, V not divisible
by block_v, and sparse/dense regimes; the Pallas path runs interpret-mode
off-TPU, i.e. the same kernel that compiles on TPU.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.graphs import generators as gen
from repro.graphs.coo import (INF_D, apply_batch, from_edges, make_batch,
                              to_numpy_adj)
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.batch import (batch_repair, batch_search_basic,
                              batch_search_improved, batchhl_update,
                              batchhl_update_split, repair_base,
                              repair_step, repair_streams,
                              search_basic_step, search_improved_step,
                              uhl_update)
from repro.core.engine import (JNP_PLAN, RelaxEngine, RelaxPlan, relax_sweep,
                               sweep_streams)
from repro.kernels.edge_relax import ops as er_ops
from repro.core.labelling import INF_KEY2, INF_KEY4
from repro.core.query import batched_query, bounded_bibfs
from repro.core import ref

# Heavy parity matrix (interpret-mode Pallas on every call-site): the fast
# CI job skips it; the full job and tier-1 run it all.
pytestmark = pytest.mark.slow

# (n, extra_edges, block_v): small-V, non-divisible-by-block, tiny-block.
SHAPES = [(9, 4, 8), (30, 15, 16), (57, 30, 16), (64, 40, 32)]


def _instance(seed: int, n: int, extra: int, r: int = 3):
    edges = gen.random_connected(n, extra_edges=extra, seed=seed)
    g = from_edges(n, edges, edges.shape[0] + 32)
    landmarks = select_landmarks_by_degree(g, r)
    lab = build_labelling(g, landmarks)
    return edges, g, landmarks, lab


def _plan(g, block_v) -> RelaxPlan:
    return RelaxEngine(backend="pallas", block_v=block_v).prepare(g)


# --- raw sweep primitive ----------------------------------------------------

@pytest.mark.parametrize("n,extra,bv", SHAPES)
@pytest.mark.parametrize("step,inf,clear", [
    (1, int(INF_D), 0),          # Algo-2 / BiBFS waves
    (2, int(INF_KEY2), 1),       # key2: construction / Algo-4 repair
    (4, int(INF_KEY4), 2),       # key4: Algo-3 improved search
])
def test_sweep_parity(n, extra, bv, step, inf, clear):
    edges, g, _, _ = _instance(n + extra, n, extra)
    plan = _plan(g, bv)
    rng = np.random.default_rng(n * 7 + step)
    keys = jnp.asarray(rng.integers(0, inf, n, endpoint=True)
                       .astype(np.int32))
    hub = jnp.asarray(rng.random(n) < 0.3)
    mask = jnp.asarray(rng.random(g.src.shape[0]) < 0.7) & g.valid
    want = relax_sweep(JNP_PLAN, g, keys, step, inf,
                       hub=hub, clear_bit=clear, edge_mask=mask)
    got = relax_sweep(plan, g, keys, step, inf,
                      hub=hub, clear_bit=clear, edge_mask=mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sweep_parity_vmapped_planes():
    """The hot paths vmap sweeps over landmark planes; parity must hold
    with keys, hub, and edge masks all batched."""
    n, extra, bv, r = 41, 25, 16, 4
    edges, g, _, _ = _instance(11, n, extra)
    plan = _plan(g, bv)
    rng = np.random.default_rng(11)
    keys = jnp.asarray(rng.integers(0, 200, (r, n)).astype(np.int32))
    hub = jnp.asarray(rng.random((r, n)) < 0.2)
    mask = jnp.asarray(rng.random((r, g.src.shape[0])) < 0.8) & g.valid

    def run(plan):
        return jax.vmap(
            lambda k, h, m: relax_sweep(plan, g, k, 2, jnp.int32(INF_KEY2),
                                        hub=h, clear_bit=1, edge_mask=m)
        )(keys, hub, mask)

    np.testing.assert_array_equal(np.asarray(run(plan)),
                                  np.asarray(run(JNP_PLAN)))


# --- phase streams: built once ≡ built in every wave ----------------------

STREAM_PLANS = {  # plan kind → (block_e, tile shards); None: not tiled
    "jnp": None, "sorted": None, "kernel": (None, 1),
    "kernel_chunked": (8, 1), "kernel_shards2": (None, 2)}


def _streams_plan(g, kind):
    src, dst, keep = np.asarray(g.src), np.asarray(g.dst), np.asarray(g.valid)
    if kind == "jnp":
        return JNP_PLAN
    if kind == "sorted":
        return RelaxPlan(tiles=None, backend="pallas", impl="sorted",
                         sorted_tiles=er_ops.prepare_sorted(src, dst, keep,
                                                            g.n))
    block_e, shards = STREAM_PLANS[kind]
    return RelaxPlan(er_ops.prepare_topology(src, dst, keep, g.n, 16, shards,
                                             block_e), "pallas")


@pytest.mark.parametrize("mutation", ["deletions", "reweights"])
@pytest.mark.parametrize("kind", sorted(STREAM_PLANS))
@pytest.mark.parametrize("step", ["search_improved_step", "search_basic_step",
                                  "repair_base", "repair_step"])
def test_phase_streams_match_per_wave_streams(step, kind, mutation):
    """A fixpoint phase's sweep streams, built once and read by every
    wave, give every plane after every wave bit for bit what building
    them inside each wave gives: on each backend and tiling, with edges
    deleted since the tiling or re-weighted (w ≠ 1) since it."""
    n, extra, p = 61, 90, 3
    _, g, _, _ = _instance(7, n, extra)
    plan = _streams_plan(g, kind)
    rng = np.random.default_rng(len(step) * 13 + len(kind))
    if mutation == "deletions":       # validity flips; the tiling stays
        g = dataclasses.replace(
            g, valid=g.valid & jnp.asarray(rng.random(g.valid.shape) < 0.7))
    else:
        g = dataclasses.replace(g, w=jnp.where(
            g.valid, jnp.asarray(rng.integers(1, 9, g.w.shape), jnp.int32),
            0))
    inf = {"search_improved_step": INF_KEY4,
           "search_basic_step": INF_D}.get(step, INF_KEY2)
    x0 = jnp.asarray(np.where(rng.random((p, n)) < 0.2,
                              rng.integers(0, 64, (p, n)), int(inf)),
                     jnp.int32)
    bound = jnp.asarray(rng.integers(int(inf) // 2, int(inf), (p, n)),
                        jnp.int32)
    hub_mask = jnp.asarray(rng.random((p, n)) < 0.3)
    aff = jnp.asarray(rng.random((p, n)) < 0.5)
    wave = {
        "search_improved_step": lambda x, st: search_improved_step(
            plan, g, x, x0, bound, hub_mask, st),
        "search_basic_step": lambda x, st: search_basic_step(
            plan, g, x, x0, bound, st),
        "repair_base": lambda x, st: repair_base(plan, g, aff, x, hub_mask,
                                                 st),
        "repair_step": lambda x, st: repair_step(plan, g, x, aff, hub_mask,
                                                 st),
    }[step]
    streams = sweep_streams(plan, g, hub=hub_mask)
    if step == "repair_step":
        streams = repair_streams(plan, g, aff, streams)
    wave = jax.jit(wave)
    once = each = x0
    for _ in range(3):
        once, each = wave(once, streams), wave(each, None)
        np.testing.assert_array_equal(np.asarray(once), np.asarray(each))
    assert np.any(np.asarray(once) != np.asarray(x0))


# --- the four sweep call-sites ---------------------------------------------

@pytest.mark.parametrize("n,extra,bv", SHAPES)
def test_search_and_repair_parity(n, extra, bv):
    edges, g, landmarks, lab = _instance(n, n, extra)
    ups = gen.random_batch_updates(edges, n, n_ins=3, n_del=3, seed=n + 1)
    batch = make_batch(ups, pad_to=6)
    g2 = apply_batch(g, batch)
    plan = _plan(g2, bv)

    aff_b_j = batch_search_basic(g, g2, batch, lab)
    aff_b_p = batch_search_basic(g, g2, batch, lab, plan)
    np.testing.assert_array_equal(np.asarray(aff_b_p), np.asarray(aff_b_j))

    aff_i_j = batch_search_improved(g, g2, batch, lab)
    aff_i_p = batch_search_improved(g, g2, batch, lab, plan)
    np.testing.assert_array_equal(np.asarray(aff_i_p), np.asarray(aff_i_j))

    lab_j = batch_repair(g2, aff_i_j, lab)
    lab_p = batch_repair(g2, aff_i_j, lab, plan)
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(lab_p, f)),
                                      np.asarray(getattr(lab_j, f)))


@pytest.mark.parametrize("n,extra,bv", SHAPES)
@pytest.mark.parametrize("improved", [False, True])
def test_batchhl_update_parity(n, extra, bv, improved):
    """End-to-end: identical aff sets, repaired labellings, and query
    answers on both backends."""
    edges, g, landmarks, lab = _instance(n * 2 + 1, n, extra)
    ups = gen.random_batch_updates(edges, n, n_ins=4, n_del=4, seed=n + 2)
    batch = make_batch(ups, pad_to=8)
    plan = _plan(apply_batch(g, batch), bv)

    gj, labj, affj = batchhl_update(g, batch, lab, improved=improved)
    gp, labp, affp = batchhl_update(g, batch, lab, improved=improved,
                                    plan=plan)
    np.testing.assert_array_equal(np.asarray(affp), np.asarray(affj))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(labp, f)),
                                      np.asarray(getattr(labj, f)))

    rng = np.random.default_rng(n)
    qs = jnp.asarray(rng.integers(0, n, 12), jnp.int32)
    qt = jnp.asarray(rng.integers(0, n, 12), jnp.int32)
    dj = batched_query(gj, labj, qs, qt)
    dp = batched_query(gp, labp, qs, qt, plan=plan)
    np.testing.assert_array_equal(np.asarray(dp), np.asarray(dj))


def test_pallas_update_matches_oracle():
    """Not just parity: the Pallas path agrees with the from-scratch BFS
    oracle on the repaired labelling and on exact query answers."""
    n = 34
    edges, g, landmarks, lab = _instance(21, n, 17)
    ups = gen.random_batch_updates(edges, n, n_ins=3, n_del=3, seed=5)
    batch = make_batch(ups, pad_to=6)
    plan = _plan(apply_batch(g, batch), 16)
    g2, lab2, _ = batchhl_update(g, batch, lab, improved=True, plan=plan)

    adj2 = to_numpy_adj(g2)
    od, oh, ohw, omask = ref.minimal_labelling(
        adj2, n, [int(x) for x in np.asarray(landmarks)])
    jd = np.asarray(lab2.dist)
    for i in range(len(np.asarray(landmarks))):
        for v in range(n):
            want = od[i][v] if od[i][v] != ref.INF else int(INF_D)
            assert jd[i, v] == want, (i, v)

    rng = np.random.default_rng(3)
    qs = rng.integers(0, n, 16).astype(np.int32)
    qt = rng.integers(0, n, 16).astype(np.int32)
    got = np.asarray(batched_query(g2, lab2, jnp.asarray(qs),
                                   jnp.asarray(qt), plan=plan))
    for k in range(16):
        want = ref.pair_distance(adj2, n, int(qs[k]), int(qt[k]))
        want = 0 if qs[k] == qt[k] else want
        want = int(INF_D) if want == ref.INF else want
        assert got[k] == want


@pytest.mark.parametrize(
    "n,extra,bv,max_steps",
    [(*shape, 32) for shape in SHAPES] + [(*SHAPES[2], 0)],
    ids=[f"{n}-{e}-{bv}" for n, e, bv in SHAPES] + ["capped0"])
def test_bibfs_parity(n, extra, bv, max_steps):
    """Distances and the wave counters agree across backends; a lane is
    live in at most every wave, and a 0-wave cap runs none."""
    edges, g, landmarks, lab = _instance(n + 5, n, extra)
    plan = _plan(g, bv)
    rng = np.random.default_rng(n)
    s = jnp.asarray(rng.integers(0, n, 10), jnp.int32)
    t = jnp.asarray(rng.integers(0, n, 10), jnp.int32)
    bound = jnp.full((10,), INF_D, jnp.int32)
    dj, wj, lj, _ = bounded_bibfs(g, lab.landmarks, s, t, bound, max_steps)
    dp, wp, lp, _ = bounded_bibfs(g, lab.landmarks, s, t, bound, max_steps,
                                  plan)
    np.testing.assert_array_equal(np.asarray(dp), np.asarray(dj))
    assert int(wp) == int(wj) <= max_steps
    np.testing.assert_array_equal(np.asarray(lp), np.asarray(lj))
    assert np.all((np.asarray(lj) >= 0) & (np.asarray(lj) <= int(wj)))
    if max_steps == 0:
        assert int(wj) == 0
    else:
        # the slowest lane is live in every wave of the loop
        assert int(wj) > 0 and int(np.asarray(lj).max()) == int(wj)


@pytest.mark.parametrize("n,extra,bv", SHAPES)
def test_construction_parity(n, extra, bv):
    edges, g, landmarks, _ = _instance(n + 9, n, extra)
    plan = _plan(g, bv)
    lab_j = build_labelling(g, landmarks)
    lab_p = build_labelling(g, landmarks, plan=plan)
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(lab_p, f)),
                                      np.asarray(getattr(lab_j, f)))


def _assert_labelling_matches_oracle(g2, landmarks, lab2):
    """Repaired dist planes must equal the from-scratch BFS oracle's."""
    adj2 = to_numpy_adj(g2)
    n = g2.n
    od, _, _, _ = ref.minimal_labelling(
        adj2, n, [int(x) for x in np.asarray(landmarks)])
    jd = np.asarray(lab2.dist)
    for i in range(len(np.asarray(landmarks))):
        for v in range(n):
            want = od[i][v] if od[i][v] != ref.INF else int(INF_D)
            assert jd[i, v] == want, (i, v)


@pytest.mark.parametrize("variant", ["split", "unit"])
def test_split_and_unit_variants_parity(variant):
    """BHL^s and UHL+ take the engine (per-sub-batch tiling) — their
    results must match the jnp reference bit-for-bit on every labelling
    field AND the from-scratch BFS oracle on the final snapshot."""
    n = 28
    edges, g, landmarks, lab = _instance(13, n, 14)
    ups = gen.random_batch_updates(edges, n, n_ins=3, n_del=3, seed=17)
    batch = make_batch(ups, pad_to=6)
    engine = RelaxEngine(backend="pallas", block_v=16)
    update = batchhl_update_split if variant == "split" else uhl_update

    g_j, lab_j, aff_j = update(g, batch, lab)
    g_p, lab_p, aff_p = update(g, batch, lab, engine=engine)
    np.testing.assert_array_equal(np.asarray(aff_p), np.asarray(aff_j))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(lab_p, f)),
                                      np.asarray(getattr(lab_j, f)))
    np.testing.assert_array_equal(np.asarray(g_p.valid),
                                  np.asarray(g_j.valid))
    # Oracle correctness (not just backend parity) for both variants, on
    # both backends (they were just asserted identical).
    _assert_labelling_matches_oracle(g_j, landmarks, lab_j)

    # ...and exact query answers from the engine-driven labelling.
    rng = np.random.default_rng(n)
    qs = rng.integers(0, n, 12).astype(np.int32)
    qt = rng.integers(0, n, 12).astype(np.int32)
    plan = engine.prepare(g_p, topology_changed=False)
    got = np.asarray(batched_query(g_p, lab_p, jnp.asarray(qs),
                                   jnp.asarray(qt), plan=plan))
    adj2 = to_numpy_adj(g_j)
    for k in range(12):
        want = ref.pair_distance(adj2, n, int(qs[k]), int(qt[k]))
        want = 0 if qs[k] == qt[k] else want
        want = int(INF_D) if want == ref.INF else want
        assert got[k] == want


# --- tiling-cache contract --------------------------------------------------

def test_engine_retile_cache():
    """Deletion-only ticks reuse the tiling; insertions force a rebuild;
    the jnp backend never tiles (no host syncs)."""
    n = 26
    edges, g, landmarks, lab = _instance(19, n, 13)
    engine = RelaxEngine(backend="pallas", block_v=16)
    plan0 = engine.prepare(g)
    assert engine.retile_count == 1

    # deletion-only: cache hit, tiles object unchanged
    dele = make_batch([(int(edges[0][0]), int(edges[0][1]), True)], pad_to=1)
    g2 = apply_batch(g, dele)
    plan1 = engine.prepare(g2, topology_changed=False)
    assert engine.retile_count == 1
    assert plan1.tiles is plan0.tiles
    # ...and the reused tiling still gives correct (jnp-identical) results
    gj, labj, affj = batchhl_update(g, dele, lab)
    gp, labp, affp = batchhl_update(g, dele, lab, plan=plan1)
    np.testing.assert_array_equal(np.asarray(affp), np.asarray(affj))
    np.testing.assert_array_equal(np.asarray(labp.dist),
                                  np.asarray(labj.dist))

    # insertion: topology slots rewritten → retile
    ins = make_batch([(0, n - 1, False)], pad_to=1)
    g3 = apply_batch(g2, ins)
    plan2 = engine.prepare(g3, topology_changed=True)
    assert engine.retile_count == 2
    assert plan2.tiles is not plan0.tiles

    jnp_engine = RelaxEngine(backend="jnp")
    assert jnp_engine.prepare(g).tiles is None
    assert jnp_engine.retile_count == 0


def test_engine_prepare_catches_stale_cache():
    """prepare(topology_changed=False) after slots actually changed (or on
    a different graph entirely) must retile, not silently serve stale
    tiles — the snapshot fingerprint recorded at tiling time catches it."""
    n = 26
    edges, g, landmarks, lab = _instance(19, n, 13)
    engine = RelaxEngine(backend="pallas", block_v=16)
    engine.prepare(g)
    assert engine.retile_count == 1

    # An insertion rewrites topology slots; the caller *lies* about it.
    ins = make_batch([(0, n - 1, False), (1, n - 2, False)], pad_to=2)
    g2 = apply_batch(g, ins)
    plan = engine.prepare(g2, topology_changed=False)
    assert engine.retile_count == 2, "stale tiling served for new topology"
    assert engine.stale_cache_retiles == 1
    # ...and the (re)tiled plan gives correct distances on the new graph.
    lab_j = build_labelling(g2, landmarks)
    lab_p = build_labelling(g2, landmarks, plan=plan)
    np.testing.assert_array_equal(np.asarray(lab_p.dist),
                                  np.asarray(lab_j.dist))

    # A different graph entirely (same n/capacity) also mismatches.
    other = gen.random_connected(n, extra_edges=13, seed=99)
    g_other = from_edges(n, other, g.capacity)
    engine.prepare(g_other, topology_changed=False)
    assert engine.stale_cache_retiles == 2

    # Legitimate deletion-only reuse still hits the cache.
    dele = make_batch([(int(other[0][0]), int(other[0][1]), True)], pad_to=1)
    engine.prepare(apply_batch(g_other, dele), topology_changed=False)
    assert engine.retile_count == 3  # unchanged by the deletion-only call
    assert engine.stale_cache_retiles == 2


def test_fingerprint_distinguishes_slot_layouts():
    """Regression (found by the batch-split property test): whole-batch
    vs split-batch application ends with the same edge multiset in
    *different slot layouts*. A slot-position-insensitive checksum keys
    them to the same cached tiling, whose embedded slot permutation then
    re-tiles the wrong graph's validity mask — distances go to INF. The
    fingerprint must differ whenever slot layout differs."""
    n, n_ins, n_del = 18, 3, 2
    edges = gen.random_connected(n, extra_edges=n // 2, seed=0)
    g = from_edges(n, edges, edges.shape[0] + 16)
    ups = gen.random_batch_updates(edges, n, n_ins=n_ins, n_del=n_del,
                                   seed=3)
    g_whole = apply_batch(g, make_batch(ups, pad_to=len(ups)))
    j = len(ups) // 2
    g_split = apply_batch(apply_batch(g, make_batch(ups[:j], pad_to=j)),
                          make_batch(ups[j:], pad_to=len(ups) - j))
    # Same edge set, different slot layout (the collision precondition).
    assert to_numpy_adj(g_whole) == to_numpy_adj(g_split)
    assert not np.array_equal(np.asarray(g_whole.src),
                              np.asarray(g_split.src))
    fp_w = RelaxEngine._snapshot_fingerprint(g_whole)
    fp_s = RelaxEngine._snapshot_fingerprint(g_split)
    assert fp_w != fp_s

    # Behavioral pin: preparing both layouts through ONE engine (shared
    # plan cache) must yield jnp-identical updates for each.
    landmarks = select_landmarks_by_degree(g, 3)
    lab = build_labelling(g, landmarks)
    engine = RelaxEngine(backend="pallas", block_v=16)
    batch_w = make_batch(ups, pad_to=len(ups))
    plan_w = engine.prepare(g_whole)
    ups_b = ups[j:]
    batch_a = make_batch(ups[:j], pad_to=j)
    g_a = apply_batch(g, batch_a)
    plan_a = engine.prepare(g_a)
    _, lab_a, _ = batchhl_update(g, batch_a, lab, plan=plan_a, g_new=g_a)
    plan_s = engine.prepare(g_split)
    batch_b = make_batch(ups_b, pad_to=len(ups_b))
    _, lab_s, _ = batchhl_update(g_a, batch_b, lab_a, plan=plan_s,
                                 g_new=g_split)
    _, lab_w, _ = batchhl_update(g, batch_w, lab, plan=plan_w,
                                 g_new=g_whole)
    np.testing.assert_array_equal(np.asarray(lab_s.dist),
                                  np.asarray(lab_w.dist))
    np.testing.assert_array_equal(np.asarray(lab_s.hub),
                                  np.asarray(lab_w.hub))


def test_engine_backend_validation():
    with pytest.raises(ValueError):
        RelaxEngine(backend="cuda")
    with pytest.raises(ValueError):
        RelaxEngine(backend="pallas", shards=0)
    edges, g, _, _ = _instance(2, 12, 6)
    bad = RelaxPlan(tiles=None, backend="nope")
    with pytest.raises(ValueError):
        relax_sweep(bad, g, jnp.zeros(12, jnp.int32), 1, int(INF_D))


def test_plan_survives_mesh_roundtrip():
    """Regression for the old `shard_gate` downgrade, which dropped the
    plan object entirely under a mesh: one prepared plan must serve a
    sharded update and then an unsharded call *without* retiling — the
    fingerprint check recognizes the (deletion-only) snapshot as the one
    it tiled."""
    from repro.core.shard import shard_batchhl_update
    from repro.launch.mesh import make_host_mesh

    n = 40
    edges, g, landmarks, lab = _instance(23, n, 20, r=8)
    engine = RelaxEngine(backend="pallas", block_v=16, shards=2)
    plan0 = engine.prepare(g)
    assert engine.retile_count == 1

    dele = make_batch([(int(edges[0][0]), int(edges[0][1]), True),
                       (int(edges[1][0]), int(edges[1][1]), True)], pad_to=2)
    mesh = make_host_mesh()
    sg, slab, saff = shard_batchhl_update(mesh, g, batch=dele, labelling=lab,
                                          plan=plan0)

    # Post-mesh, single-device: same tiles object, no retile, no stale
    # catch — the mesh leg never invalidated the cache.
    plan1 = engine.prepare(sg, topology_changed=False)
    assert plan1.tiles is plan0.tiles
    assert engine.retile_count == 1
    assert engine.stale_cache_retiles == 0
    gj, labj, affj = batchhl_update(g, dele, lab)
    gp, labp, affp = batchhl_update(g, dele, lab, plan=plan1)
    np.testing.assert_array_equal(np.asarray(affp), np.asarray(affj))
    np.testing.assert_array_equal(np.asarray(labp.dist),
                                  np.asarray(labj.dist))
    # ...and the sharded leg itself matched the unsharded jnp reference.
    np.testing.assert_array_equal(np.asarray(saff), np.asarray(affj))
    np.testing.assert_array_equal(np.asarray(slab.dist),
                                  np.asarray(labj.dist))


# --- three-way backend × mesh parity sweep ---------------------------------

@pytest.mark.parametrize("mode", ["insert", "delete", "mixed"])
def test_three_way_backend_mesh_parity(mode):
    """sharded-pallas ≡ sharded-jnp ≡ unsharded-jnp, bit-for-bit, on
    insert-only, delete-only, and mixed batches — labelling fields,
    affected sets, and query answers."""
    from repro.core.shard import shard_batched_query, shard_batchhl_update
    from repro.launch.mesh import make_host_mesh

    n = 48
    edges, g, landmarks, lab = _instance(29, n, 30, r=8)
    n_ins, n_del = {"insert": (5, 0), "delete": (0, 5),
                    "mixed": (3, 3)}[mode]
    ups = gen.random_batch_updates(edges, n, n_ins=n_ins, n_del=n_del,
                                   seed=37)
    batch = make_batch(ups, pad_to=max(n_ins + n_del, 1))
    g_next = apply_batch(g, batch)
    plan = RelaxEngine(backend="pallas", block_v=16, shards=2).prepare(g_next)
    mesh = make_host_mesh()

    g_u, lab_u, aff_u = batchhl_update(g, batch, lab, improved=True)
    g_sj, lab_sj, aff_sj = shard_batchhl_update(mesh, g, batch, lab,
                                                g_new=g_next)
    g_sp, lab_sp, aff_sp = shard_batchhl_update(mesh, g, batch, lab,
                                                plan=plan, g_new=g_next)

    for name, aff, labx in (("sharded-jnp", aff_sj, lab_sj),
                            ("sharded-pallas", aff_sp, lab_sp)):
        np.testing.assert_array_equal(np.asarray(aff), np.asarray(aff_u),
                                      err_msg=name)
        for f in ("dist", "hub", "highway"):
            np.testing.assert_array_equal(np.asarray(getattr(labx, f)),
                                          np.asarray(getattr(lab_u, f)),
                                          err_msg=f"{name}.{f}")

    rng = np.random.default_rng(n)
    qs = jnp.asarray(rng.integers(0, n, 19), jnp.int32)
    qt = jnp.asarray(rng.integers(0, n, 19), jnp.int32)
    d_u = batched_query(g_u, lab_u, qs, qt)
    d_sj = shard_batched_query(mesh, g_sj, lab_sj, qs, qt)
    d_sp = shard_batched_query(mesh, g_sp, lab_sp, qs, qt,
                               use_kernel=True, plan=plan)
    np.testing.assert_array_equal(np.asarray(d_sj), np.asarray(d_u))
    np.testing.assert_array_equal(np.asarray(d_sp), np.asarray(d_u))
