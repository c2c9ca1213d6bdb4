"""The versioned-snapshot serving pipeline (DESIGN.md §5): chunked
updates are bit-identical to the monolithic BatchHL step, pipelined
serving answers are exact at the version each query was served, full
checkpoints resume the loop exactly, and the scenario registry / mesh
validation behave.

The forced-8-device coverage lives in `repro.core.snapshot._selftest`
(subprocess, slow-marked below) — the in-process tests here run on
whatever devices the session has (1 in plain CI, 8 in the mesh job)."""
from __future__ import annotations

import math
import os
import subprocess
import sys
from functools import partial
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.graphs import generators as gen
from repro.graphs.coo import (OP_DEL, OP_INS, OP_REW, apply_batch,
                              from_edges, make_batch, resolve_seed_weights,
                              to_numpy_adj)
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.batch import batchhl_update
from repro.core.engine import RelaxEngine
from repro.core.query import batched_query
from repro.core.shard import validate_landmark_sharding
from repro.core.snapshot import (Snapshot, SnapshotStore, pipelined_update,
                                 repair_chunk, repair_start,
                                 restore_snapshot, run_pipelined_update,
                                 save_snapshot, search_chunk, search_seed)
from repro.checkpoint import manager as ckpt
from repro.data.scenarios import SCENARIOS, get_scenario
from repro.launch import trace
from repro.launch.serve import ServeConfig, ServeLoop

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _instance(seed=3, n=150, extra=200, r=8):
    edges = gen.random_connected(n, extra_edges=extra, seed=seed)
    g = from_edges(n, edges, edges.shape[0] + 64)
    landmarks = select_landmarks_by_degree(g, r)
    lab = build_labelling(g, landmarks)
    ups = gen.random_batch_updates(edges, n, n_ins=8, n_del=8, seed=9)
    return g, lab, make_batch(ups, pad_to=16)


# --- chunked update ≡ monolithic update ------------------------------------

@pytest.mark.parametrize("improved", [True, False])
@pytest.mark.parametrize("chunk_sweeps", [1, 2, 3])
def test_pipelined_update_matches_monolithic(improved, chunk_sweeps):
    g, lab, batch = _instance()
    gm, labm, affm = batchhl_update(g, batch, lab, improved=improved)
    nxt, aff = run_pipelined_update(pipelined_update(
        Snapshot(0, g, lab, None), batch, improved=improved,
        chunk_sweeps=chunk_sweeps))
    assert nxt.version == 1
    np.testing.assert_array_equal(np.asarray(aff), np.asarray(affm))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(nxt.labelling, f)),
            np.asarray(getattr(labm, f)))
    np.testing.assert_array_equal(np.asarray(nxt.graph.valid),
                                  np.asarray(gm.valid))


def _weighted_instance(seed=4, n=120, extra=160, r=8, max_w=6):
    edges = gen.random_connected(n, extra_edges=extra, seed=seed)
    w = np.random.default_rng(seed + 1).integers(1, max_w + 1,
                                                 size=edges.shape[0])
    ew = np.concatenate([edges, w[:, None]], axis=1).astype(np.int32)
    g = from_edges(n, ew, edges.shape[0] + 64)
    lab = build_labelling(g, select_landmarks_by_degree(g, r))
    return g, lab, ew


@pytest.mark.parametrize("mix", ["deletions", "weighted_deletions_reweights"])
def test_pipelined_update_matches_monolithic_on_deletions(mix):
    """Drained dry, the chunked update commits what `batchhl_update` does
    on a batch whose seeds take the pre-update weights (deletions, and
    re-weights at min(old, new)): the seed-weight resolution runs as a
    program of its own here and inside the monolith's there."""
    if mix == "deletions":
        g, lab, _ = _instance()
        edges = np.stack([np.asarray(g.src), np.asarray(g.dst)], 1)[
            np.asarray(g.valid)][::2]
        ups = gen.random_batch_updates(edges, g.n, n_ins=0, n_del=12,
                                       seed=5)
    else:
        g, lab, ew = _weighted_instance()
        ups = gen.random_batch_updates(ew, g.n, n_ins=4, n_del=8, seed=6,
                                       n_rew=8, max_weight=6)
        assert any(int(u[2]) == 2 for u in ups)
    batch = make_batch(ups, pad_to=24)
    gm, labm, affm = batchhl_update(g, batch, lab)
    nxt, aff = run_pipelined_update(pipelined_update(
        Snapshot(0, g, lab, None), batch))
    np.testing.assert_array_equal(np.asarray(aff), np.asarray(affm))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(nxt.labelling, f)),
            np.asarray(getattr(labm, f)))
    np.testing.assert_array_equal(np.asarray(nxt.graph.w),
                                  np.asarray(gm.w))


def test_resolve_seed_weights_semantics():
    """Insert → its new weight; delete → the edge's weight in G; re-weight
    → min(old, new); an unmatched row and a padding row → 1."""
    ew = np.array([[0, 1, 5], [1, 2, 2], [2, 3, 4]], np.int32)
    g = from_edges(5, ew, 6)
    batch = make_batch([(3, 4, OP_INS, 3),      # insert
                        (1, 0, OP_DEL, 9),      # delete, reversed ends
                        (2, 3, OP_REW, 7),      # re-weight up: old 4
                        (1, 2, OP_REW, 1),      # re-weight down: new 1
                        (0, 4, OP_DEL, 9),      # no such edge
                        (0, 3, OP_REW, 6)],     # no such edge
                       pad_to=8)
    got = resolve_seed_weights(g, batch)
    np.testing.assert_array_equal(np.asarray(got.w),
                                  [3, 5, 4, 1, 1, 1, 1, 1])
    for f in ("src", "dst", "is_del", "valid", "is_rew"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(batch, f)))


def _pallas_plan(g_next, impl: str):
    """A Pallas plan of `impl`: the tiled kernel tiling, or the
    autotuned dst-sorted twin."""
    if impl == "kernel":
        engine = RelaxEngine(backend="pallas", block_v=32, shards=2)
    else:
        engine = RelaxEngine(backend="pallas", block_v=32, autotune=True)
    plan = engine.prepare(g_next)
    assert plan.impl == impl
    return plan


@pytest.mark.parametrize("chunk_sweeps", [1, 2])
@pytest.mark.parametrize("impl", ["kernel", "sorted"])
def test_pipelined_update_pallas_plan(impl, chunk_sweeps):
    """The chunked path composes with both Pallas plan impls, at one
    sweep a chunk (the served configuration) and at two."""
    g, lab, batch = _instance()
    gm, labm, affm = batchhl_update(g, batch, lab)
    g_next = apply_batch(g, batch)
    plan = _pallas_plan(g_next, impl)
    nxt, aff = run_pipelined_update(pipelined_update(
        Snapshot(0, g, lab, None), batch, plan=plan, g_new=g_next,
        chunk_sweeps=chunk_sweeps))
    np.testing.assert_array_equal(np.asarray(aff), np.asarray(affm))
    np.testing.assert_array_equal(np.asarray(nxt.labelling.dist),
                                  np.asarray(labm.dist))


@pytest.mark.parametrize("chunk_sweeps", [1, 2])
def test_pipelined_update_mesh_matches(chunk_sweeps):
    """Mesh chunks (maintenance plane grouping, pmax convergence) ≡
    unsharded monolith on this session's device mesh; the full
    factorization sweep lives in `repro.core.snapshot._selftest`."""
    from repro.launch.mesh import make_host_mesh
    g, lab, batch = _instance()
    gm, labm, affm = batchhl_update(g, batch, lab)
    nxt, aff = run_pipelined_update(pipelined_update(
        Snapshot(0, g, lab, None), batch, mesh=make_host_mesh(),
        chunk_sweeps=chunk_sweeps))
    np.testing.assert_array_equal(np.asarray(aff), np.asarray(affm))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(nxt.labelling, f)),
            np.asarray(getattr(labm, f)))


def test_pipelined_donation_safety():
    """`repair_start` donates the search phase's streams: the donation
    must never free a live buffer. Running the identical update twice
    from one snapshot and one Pallas plan gives the same bits, and the
    input labelling and the plan's tiles survive both runs untouched (a
    stream that aliased a plan or labelling buffer would be freed by the
    first run and corrupt, or fail, the second)."""
    g, lab, batch = _instance()
    g_next = apply_batch(g, batch)
    plan = _pallas_plan(g_next, "kernel")
    before = {f: np.array(getattr(lab, f)) for f in ("dist", "hub",
                                                     "highway")}
    tiles = [np.array(x) for x in jax.tree.leaves(plan)]
    outs = []
    for _ in range(2):
        nxt, aff = run_pipelined_update(pipelined_update(
            Snapshot(0, g, lab, None), batch, plan=plan, g_new=g_next))
        outs.append((np.asarray(aff),
                     {f: np.asarray(getattr(nxt.labelling, f))
                      for f in before}))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    for f in before:
        np.testing.assert_array_equal(outs[0][1][f], outs[1][1][f])
        np.testing.assert_array_equal(np.asarray(getattr(lab, f)),
                                      before[f])
    for want, got in zip(tiles, jax.tree.leaves(plan), strict=True):
        np.testing.assert_array_equal(np.asarray(got), want)


# --- pipelined serving: exact at the served version ------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_pipeline_serving_exact_at_version(backend):
    """Every answered query equals the synchronous `batched_query` at the
    snapshot version it was served — the staleness contract."""
    cfg = ServeConfig(n=200, deg=3, landmarks=8, batches=3, batch_size=20,
                      queries=24, qps=5000.0, microbatch=8, pipeline=True,
                      backend=backend, block_v=64, tile_shards=2,
                      quiet=True, keep_history=True)
    rep = ServeLoop(cfg).run()
    assert sum(m.qs.shape[0] for m in rep.microbatches) == 3 * 24
    for m in rep.microbatches:
        snap = rep.history[m.version]
        want = batched_query(snap.graph, snap.labelling,
                             jnp.asarray(m.qs), jnp.asarray(m.qt))
        np.testing.assert_array_equal(m.answers, np.asarray(want))
        # the BiBFS counters ride along on either backend
        assert 0 < m.waves <= 64
        assert 0 <= m.live_lane_waves <= m.waves * m.qs.shape[0]
    # the pipeline actually overlapped: some answers were served against
    # the stale committed snapshot while the update was in flight
    assert any(m.staleness == 1 for m in rep.microbatches)
    assert all(m.staleness in (0, 1) for m in rep.microbatches)


def test_pipeline_and_sync_commit_identical_labellings():
    """Same stream, both modes: per-tick committed state is bit-equal
    (the pipeline changes *when* queries are answered, never the data)."""
    base = dict(n=200, deg=3, landmarks=8, batches=3, batch_size=20,
                queries=16, qps=5000.0, microbatch=8, quiet=True,
                keep_history=True)
    rep_s = ServeLoop(ServeConfig(**base, pipeline=False)).run()
    rep_p = ServeLoop(ServeConfig(**base, pipeline=True)).run()
    assert rep_s.final.version == rep_p.final.version == 3
    for v in range(4):
        for f in ("dist", "hub", "highway"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rep_s.history[v].labelling, f)),
                np.asarray(getattr(rep_p.history[v].labelling, f)))
        np.testing.assert_array_equal(
            np.asarray(rep_s.history[v].graph.valid),
            np.asarray(rep_p.history[v].graph.valid))
    # identical query streams, answered in full by both modes
    np.testing.assert_array_equal(
        np.concatenate([m.qs for m in rep_s.microbatches]),
        np.concatenate([m.qs for m in rep_p.microbatches]))
    # sync never serves stale; pipeline reports staleness honestly
    assert all(m.staleness == 0 for m in rep_s.microbatches)


def test_update_waves_and_stream_builds_counted(capsys):
    """Both serving modes count the same fixpoint waves for the same
    batch (one wave a chunk), build two stream sets a tick, and print
    both in the tick log and the published run record."""
    base = dict(n=200, deg=3, landmarks=8, batches=2, batch_size=20,
                queries=8, qps=5000.0, microbatch=8)
    rep_s = ServeLoop(ServeConfig(**base, pipeline=False)).run()
    rec_s = trace.last_run()
    rep_p = ServeLoop(ServeConfig(**base, pipeline=True)).run()
    rec_p = trace.last_run()
    for ts, tp in zip(rep_s.ticks, rep_p.ticks, strict=True):
        assert list(ts.update_waves) == ["search", "repair"]
        assert min(ts.update_waves.values()) >= 1
        assert ts.update_waves == tp.update_waves
        assert tp.update_waves == {"search": tp.update_chunks["search"],
                                   "repair": tp.update_chunks["repair"]}
        assert ts.stream_builds == tp.stream_builds == 2
    for rec, rep in ((rec_s, rep_s), (rec_p, rep_p)):
        assert rec.update_waves == tuple(t.update_waves for t in rep.ticks)
        assert rec.stream_builds == (2, 2)
    out = capsys.readouterr().out
    for t in rep_s.ticks + rep_p.ticks:
        assert (f"tick {t.tick}: " in out and
                f"update waves {t.update_waves}, 2 stream builds" in out)


def _tile_gathers(jaxpr, shape_size: int) -> int:
    """Gathers in `jaxpr`, nested programs included, whose 4-D output
    holds `shape_size` elements."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out = eqn.outvars[0].aval.shape
            count += len(out) == 4 and math.prod(out) == shape_size
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, ClosedJaxpr):
                    count += _tile_gathers(sub.jaxpr, shape_size)
                elif isinstance(sub, Jaxpr):
                    count += _tile_gathers(sub, shape_size)
    return count


@pytest.mark.parametrize("sweeps", [1, 2])
@pytest.mark.parametrize("program", ["search_chunk", "repair_chunk"])
def test_chunk_wave_gathers_only_source_keys(program, sweeps):
    """A chunk's wave gathers one [planes × tile slots] operand, its
    source keys: the mask, weight and hub-flag streams come built from
    the phase's first dispatch, never re-gathered per wave or plane."""
    g, lab, batch = _instance(n=60, extra=80, r=4)
    g_new = apply_batch(g, batch)
    plan = RelaxEngine(backend="pallas", block_v=16).prepare(g_new)
    seed, seeded, bound, hub_mask, streams = search_seed(
        g_new, batch, lab.dist, lab.hub, lab.landmarks, plan)
    if program == "search_chunk":
        fn = partial(search_chunk, improved=True, sweeps=sweeps)
        args = (g_new, seed, seed, bound, hub_mask, plan, streams)
    else:
        aff = seeded | (seed < jnp.max(seed))
        _, streams, _ = repair_start(g_new, aff, lab.dist, lab.hub,
                                     hub_mask, plan, streams)
        fn = partial(repair_chunk, sweeps=sweeps)
        args = (g_new, lab.key2(), aff, hub_mask, plan, streams)
    planes = lab.dist.shape[0]
    slots = math.prod(plan.tiles.src_t.shape)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _tile_gathers(jaxpr, planes * slots) == sweeps


@pytest.mark.slow
def test_pipeline_selftest_multidevice():
    """Chunked-update parity on every (data, model) factorization of an
    8-device CPU mesh × both backends, plus pipelined mesh serving with
    every answer re-derived at its served version."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.core.snapshot"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "pipeline selftest OK on 8 device(s)" in out.stdout, out.stdout


# --- checkpoint / resume ---------------------------------------------------

def test_save_restore_resume_exact(tmp_path):
    """Interrupt after 2 of 4 ticks, resume in a fresh loop: identical
    final labelling, edge set, version, and per-query answers."""
    base = dict(n=200, deg=3, landmarks=8, batches=4, batch_size=20,
                queries=12, qps=5000.0, microbatch=8, quiet=True, seed=3)
    rep_a = ServeLoop(ServeConfig(**base, ckpt_dir=str(tmp_path / "a"))).run()
    ServeLoop(ServeConfig(**{**base, "batches": 2},
                          ckpt_dir=str(tmp_path / "b"))).run()
    rep_b = ServeLoop(ServeConfig(**base, ckpt_dir=str(tmp_path / "b"),
                                  resume=True)).run()
    assert rep_a.final.version == rep_b.final.version == 4
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rep_a.final.labelling, f)),
            np.asarray(getattr(rep_b.final.labelling, f)))
    # same edge *set* (capacities may differ — the short first leg sized
    # its padding for fewer ticks; content is what resume must preserve)
    assert to_numpy_adj(rep_a.final.graph) == to_numpy_adj(rep_b.final.graph)
    a_tail = [m for m in rep_a.microbatches if m.tick >= 2]
    b_tail = [m for m in rep_b.microbatches if m.tick >= 2]
    np.testing.assert_array_equal(
        np.concatenate([m.qs for m in a_tail]),
        np.concatenate([m.qs for m in b_tail]))
    np.testing.assert_array_equal(
        np.concatenate([m.answers for m in a_tail]),
        np.concatenate([m.answers for m in b_tail]))


def test_checkpoint_carries_graph_state(tmp_path):
    """The full-state checkpoint restores graph topology, not just the
    labelling — and an old labelling-only checkpoint errors clearly."""
    g, lab, batch = _instance()
    g2, lab2, _ = batchhl_update(g, batch, lab)
    snap = Snapshot(5, g2, lab2, None)
    save_snapshot(str(tmp_path / "full"), snap)
    back = restore_snapshot(str(tmp_path / "full"))
    assert back.version == 5 and back.graph.n == g2.n
    np.testing.assert_array_equal(np.asarray(back.graph.src),
                                  np.asarray(g2.src))
    np.testing.assert_array_equal(np.asarray(back.graph.valid),
                                  np.asarray(g2.valid))
    np.testing.assert_array_equal(np.asarray(back.labelling.dist),
                                  np.asarray(lab2.dist))

    ckpt.save(str(tmp_path / "old"), 1,
              {"dist": lab.dist, "hub": lab.hub, "highway": lab.highway,
               "landmarks": lab.landmarks})
    with pytest.raises(FileNotFoundError, match="graph state"):
        restore_snapshot(str(tmp_path / "old"))


def test_snapshot_store_contract():
    g, lab, _ = _instance()
    store = SnapshotStore(Snapshot(0, g, lab, None))
    assert store.version == 0
    with pytest.raises(ValueError, match="contiguous"):
        store.commit(Snapshot(2, g, lab, None))
    store.commit(Snapshot(1, g, lab, None))
    assert store.committed.version == 1


# --- engine plan keying ----------------------------------------------------

def test_engine_plan_cache_keeps_two_snapshots():
    """Alternating prepares between two live snapshots (the pipeline's
    committed-N / building-N+1 pattern) hit the keyed cache instead of
    retiling every time."""
    g, lab, batch = _instance()
    g2 = apply_batch(g, batch)
    engine = RelaxEngine(backend="pallas", block_v=32)
    p0 = engine.prepare(g)
    p1 = engine.prepare(g2)
    assert engine.retile_count == 2 and engine.plan_cache_hits == 0
    p0b = engine.prepare(g)
    p1b = engine.prepare(g2)
    assert engine.retile_count == 2, "keyed cache missed a live snapshot"
    assert engine.plan_cache_hits == 2
    assert p0b.tiles is p0.tiles and p1b.tiles is p1.tiles


# --- scenarios -------------------------------------------------------------

def test_scenario_registry():
    assert set(SCENARIOS) == {"mixed", "insert-heavy", "delete-heavy",
                              "bursty", "skewed", "growth", "traffic"}
    ins, dele, rew = get_scenario("growth").update_counts(0, 100)
    assert (ins, dele, rew) == (100, 0, 0)  # pure insertions
    with pytest.raises(ValueError, match="unknown scenario"):
        get_scenario("nope")
    ins, dele, rew = get_scenario("insert-heavy").update_counts(0, 100)
    assert (ins, dele, rew) == (90, 10, 0)
    ins, dele, rew = get_scenario("delete-heavy").update_counts(0, 100)
    assert (ins, dele, rew) == (10, 90, 0)
    bursty = get_scenario("bursty")
    assert bursty.update_counts(0, 100) == (50, 50, 0)   # burst tick
    assert sum(bursty.update_counts(1, 100)) == 10       # trickle tick
    assert bursty.max_inserts(3, 100) >= 55
    traffic = get_scenario("traffic")
    ins, dele, rew = traffic.update_counts(1, 100)
    assert rew == 75 and ins + dele == 25 and traffic.max_weight == 8
    # every 4th tick (tick > 0) is weight-change-only: zero slot churn
    assert traffic.update_counts(4, 100) == (0, 0, 100)
    assert traffic.update_counts(0, 100)[2] == 75
    rng = np.random.default_rng(0)
    qs, qt = get_scenario("skewed").sample_queries(rng, 50, 256)
    assert qs.min() >= 0 and qs.max() < 50 and qt.max() < 50
    # skew concentrates sources on low (hub) ids
    assert np.mean(qs < 5) > np.mean(qt < 5)


def test_scenarios_run_end_to_end():
    for name in ("insert-heavy", "delete-heavy", "bursty", "skewed"):
        cfg = ServeConfig(n=120, deg=3, landmarks=4, batches=2,
                          batch_size=12, queries=8, qps=5000.0,
                          microbatch=8, scenario=name, pipeline=True,
                          quiet=True, keep_history=True)
        rep = ServeLoop(cfg).run()
        assert rep.final.version == 2
        for m in rep.microbatches:
            snap = rep.history[m.version]
            want = batched_query(snap.graph, snap.labelling,
                                 jnp.asarray(m.qs), jnp.asarray(m.qt))
            np.testing.assert_array_equal(m.answers, np.asarray(want))


# --- landmark-grouping validation ------------------------------------------

def test_validate_landmark_sharding_names_failing_grouping():
    mesh24 = SimpleNamespace(shape={"data": 2, "model": 4})
    validate_landmark_sharding(mesh24, 16)               # both groupings ok
    with pytest.raises(ValueError) as e:
        validate_landmark_sharding(mesh24, 4)            # maintenance fails
    assert "maintenance grouping" in str(e.value)
    assert "query grouping" not in str(e.value)
    with pytest.raises(ValueError) as e:
        validate_landmark_sharding(mesh24, 6)            # both fail
    assert "maintenance grouping" in str(e.value)
    assert "query grouping" in str(e.value)
