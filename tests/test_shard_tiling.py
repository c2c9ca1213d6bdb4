"""Shard-aware Pallas tiling: kernel × mesh composition parity.

The tiling carries a leading vertex-shard axis ([S, NB, BE], see
`kernels/edge_relax`) and the kernel grid walks (shard, block); plans ride
into `shard_map` bodies as replicated arguments. Everything here pins the
two invariants that make `--backend pallas --mesh host` one configuration:

  1. the sweep result is bit-identical for every vertex-shard count S
     (destination blocks never straddle a shard boundary), and
  2. a Pallas plan inside a mesh produces bit-identical labellings,
     affected sets, and query answers to the unsharded jnp reference —
     including the per-shard rectangular minplus bound + pmin epilogue.

Like tests/test_shard.py, the in-process tests run on whatever host mesh
the environment provides (1 device under plain pytest, 8 under the CI
`mesh` job); instances use R=8 landmarks so plane counts divide any
device count up to 8. The subprocess test forces the 8-device platform
itself and drives the serving loop with --backend pallas against the BFS
oracle — the acceptance configuration end-to-end.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.graphs import generators as gen
from repro.graphs.coo import INF_D, apply_batch, from_edges, make_batch
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.batch import batchhl_update
from repro.core.engine import JNP_PLAN, RelaxEngine, relax_sweep
from repro.core.labelling import INF_KEY2
from repro.core.query import batched_query
from repro.core.shard import shard_batched_query, shard_batchhl_update, \
    shard_build_labelling
from repro.kernels.minplus import kernel as mpk, ref as mpr
from repro.launch.mesh import make_host_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _instance(n=60, extra=70, r=8, seed=5):
    edges = gen.random_connected(n, extra_edges=extra, seed=seed)
    g = from_edges(n, edges, edges.shape[0] + 32)
    landmarks = select_landmarks_by_degree(g, r)
    return edges, g, landmarks


# --- invariant 1: the vertex-shard axis never changes results --------------

@pytest.mark.parametrize("shards", [1, 2, 3, 5])
@pytest.mark.parametrize("n,extra,bv", [(9, 4, 8), (57, 30, 16),
                                        (64, 40, 8)])
def test_sweep_parity_across_shard_counts(shards, n, extra, bv):
    edges = gen.random_connected(n, extra_edges=extra, seed=n + shards)
    g = from_edges(n, edges, edges.shape[0] + 32)
    plan = RelaxEngine(backend="pallas", block_v=bv,
                       shards=shards).prepare(g)
    assert plan.tiles.shards == shards
    rng = np.random.default_rng(n * 31 + shards)
    keys = jnp.asarray(rng.integers(0, 200, n).astype(np.int32))
    hub = jnp.asarray(rng.random(n) < 0.3)
    mask = jnp.asarray(rng.random(g.src.shape[0]) < 0.7) & g.valid
    want = relax_sweep(JNP_PLAN, g, keys, 2, int(INF_KEY2),
                       hub=hub, clear_bit=1, edge_mask=mask)
    got = relax_sweep(plan, g, keys, 2, int(INF_KEY2),
                      hub=hub, clear_bit=1, edge_mask=mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_tiling_covers_all_edges():
    """Every occupied edge slot appears in exactly one tile slot, in
    whichever shard owns its destination block."""
    n, bv, shards = 57, 16, 3
    edges = gen.random_connected(n, extra_edges=40, seed=7)
    g = from_edges(n, edges, edges.shape[0] + 32)
    tiles = RelaxEngine(backend="pallas", block_v=bv,
                        shards=shards).prepare(g).tiles
    slot = np.asarray(tiles.slot_t)
    perm = np.asarray(tiles.perm_t)
    dstloc = np.asarray(tiles.dstloc_t)
    occupied = np.flatnonzero(np.asarray(g.valid))
    seen = perm[slot != 0]
    assert sorted(seen.tolist()) == sorted(occupied.tolist())
    # Destination reconstruction: shard/block owner matches the COO dst.
    s_idx, b_idx, e_idx = np.nonzero(slot)
    nb_loc = tiles.src_t.shape[1]
    flat_block = s_idx * nb_loc + b_idx
    dst = np.asarray(g.dst)[perm[s_idx, b_idx, e_idx]]
    np.testing.assert_array_equal(dst // bv, flat_block)
    np.testing.assert_array_equal(dst % bv, dstloc[s_idx, b_idx, e_idx])


# --- rectangular minplus: the per-shard query-bound contraction ------------

@pytest.mark.parametrize("b,p,r", [(1, 1, 1), (7, 3, 5), (64, 4, 16),
                                   (33, 128, 256), (257, 130, 64)])
def test_rectangular_minplus_kernel_parity(b, p, r):
    rng = np.random.default_rng(b * 100 + p + r)
    s = rng.integers(0, 1 << 20, (b, p)).astype(np.int32)
    h = rng.integers(0, 1 << 20, (p, r)).astype(np.int32)
    t = rng.integers(0, 1 << 20, (b, r)).astype(np.int32)
    s[rng.random((b, p)) < 0.3] = 1 << 29
    t[rng.random((b, r)) < 0.3] = 1 << 29
    got = mpk.minplus_pallas(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t),
                             interpret=True)
    want = mpr.minplus_bound(jnp.asarray(s), jnp.asarray(h), jnp.asarray(t))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_minplus_shape_mismatch_raises():
    s = jnp.zeros((4, 3), jnp.int32)
    h = jnp.zeros((5, 7), jnp.int32)
    t = jnp.zeros((4, 7), jnp.int32)
    with pytest.raises(ValueError, match="shape mismatch"):
        mpk.minplus_pallas(s, h, t, interpret=True)


# --- invariant 2: pallas plans inside the mesh ≡ unsharded jnp -------------

def test_sharded_pallas_update_parity_host_mesh():
    """shard_batchhl_update with a real tiled plan ≡ unsharded jnp on
    every labelling field, the affected sets, and query answers (with the
    per-shard minplus kernel bound)."""
    mesh = make_host_mesh()
    edges, g, landmarks = _instance(seed=21)
    n = g.n
    lab = build_labelling(g, landmarks)
    ups = gen.random_batch_updates(edges, n, n_ins=4, n_del=4, seed=9)
    batch = make_batch(ups, pad_to=8)
    g_next = apply_batch(g, batch)
    engine = RelaxEngine(backend="pallas", block_v=16, shards=2)
    plan = engine.prepare(g_next)

    gj, labj, affj = batchhl_update(g, batch, lab, improved=True)
    sgp, labp, affp = shard_batchhl_update(mesh, g, batch, lab,
                                           plan=plan, g_new=g_next)
    np.testing.assert_array_equal(np.asarray(affp), np.asarray(affj))
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(labp, f)),
                                      np.asarray(getattr(labj, f)))

    rng = np.random.default_rng(3)
    qs = jnp.asarray(rng.integers(0, n, 29), jnp.int32)
    qt = jnp.asarray(rng.integers(0, n, 29), jnp.int32)
    want = batched_query(gj, labj, qs, qt)
    got = shard_batched_query(mesh, sgp, labp, qs, qt, use_kernel=True,
                              plan=plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_pallas_construction_parity_host_mesh():
    mesh = make_host_mesh()
    _, g, landmarks = _instance(seed=31)
    plan = RelaxEngine(backend="pallas", block_v=16, shards=3).prepare(g)
    lab = build_labelling(g, landmarks)
    slab = shard_build_labelling(mesh, g, landmarks, plan=plan)
    for f in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(np.asarray(getattr(slab, f)),
                                      np.asarray(getattr(lab, f)))


def test_minplus_kernel_inside_shard_map():
    """The per-shard launch + pmin epilogue on the *kernel* path: an
    interpret-mode rectangular minplus inside a shard_map body over
    model-sharded highway rows must reproduce the full contraction."""
    import jax
    from functools import partial as fpartial
    from jax.sharding import PartitionSpec as P
    from repro.kernels.minplus import ops as minplus_ops

    mesh = make_host_mesh(model=len(jax.devices()))
    b, r = 13, 8
    rng = np.random.default_rng(2)
    s = jnp.asarray(rng.integers(0, 1000, (b, r)), jnp.int32)
    h = jnp.asarray(rng.integers(0, 1000, (r, r)), jnp.int32)
    t = jnp.asarray(rng.integers(0, 1000, (b, r)), jnp.int32)

    @fpartial(jax.jit, static_argnames=("mesh",))
    def sharded_bound(mesh, s, h, t):
        def body(s_loc, h_rows, t_full):
            part = minplus_ops.minplus_bound(s_loc, h_rows, t_full,
                                             use_pallas=True)
            return jax.lax.pmin(part, "model")

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(None, "model"), P("model"), P()),
                             out_specs=P(), check_vma=False)(s, h, t)

    want = mpr.minplus_bound(s, h, t)
    got = sharded_bound(mesh, s, h, t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_query_minplus_kernel_parity_host_mesh():
    """use_kernel=True (per-shard rectangular minplus + pmin epilogue)
    ≡ use_kernel=False ≡ unsharded, on the same labelling."""
    mesh = make_host_mesh()
    _, g, landmarks = _instance(seed=41)
    n = g.n
    lab = build_labelling(g, landmarks)
    rng = np.random.default_rng(4)
    qs = jnp.asarray(rng.integers(0, n, 17), jnp.int32)
    qt = jnp.asarray(rng.integers(0, n, 17), jnp.int32)
    want = batched_query(g, lab, qs, qt)
    got_jnp = shard_batched_query(mesh, g, lab, qs, qt, use_kernel=False)
    got_krn = shard_batched_query(mesh, g, lab, qs, qt, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(got_jnp), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_krn), np.asarray(want))


# --- acceptance configuration end-to-end (forced 8-device subprocess) ------

@pytest.mark.slow
def test_serve_pallas_mesh_multidevice():
    """`--backend pallas --mesh host` on a (data=4, model=2) 8-device CPU
    mesh: the Pallas kernel runs per shard (tile-shards=2 grid), the
    minplus kernel bounds the queries, and every answer matches the BFS
    oracle."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--n", "300", "--batches", "2", "--batch-size", "30",
         "--queries", "48", "--landmarks", "8",
         "--mesh", "host", "--shards", "2",
         "--backend", "pallas", "--tile-shards", "2",
         "--use-minplus-kernel", "--verify"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "serve loop done [backend=pallas" in out.stdout, out.stdout
    assert "tile-shards=2" in out.stdout, out.stdout
    assert out.stdout.count("verify: 0/48 mismatches") == 2, out.stdout
