"""Mesh-sharded BatchHL: construction, batch update, and queries under
`shard_map` (DESIGN.md §4).

The paper's §6 parallelism is landmark-plane parallelism: every search,
repair, and construction fixpoint is independent per landmark plane, and
Farhan et al.'s incremental follow-up confirms the independence survives
updates. The unsharded code realizes it as a single-device `vmap` over the
R axis; this module lifts the same per-plane functions onto a device mesh
(`launch/mesh.py`: `data` × `model`):

* **Maintenance** (``shard_build_labelling`` / ``shard_batchhl_update``):
  landmark planes are sharded over the ``model`` axis and — since no
  queries run mid-update — over the idle ``data`` axis too (the combined
  ``("model", "data")`` spec). Each shard runs the stock plane-slice
  fixpoints (`construct_key2_planes`, `search_*_planes`, `repair_planes`)
  on its local planes with the graph replicated: all-local, zero
  cross-shard traffic inside the wave loops. Only the highway rows leave
  the shard, assembled row-sharded by the out-spec (the "highway gather"
  happens lazily as an all-gather when a consumer needs it replicated).

* **Queries** (``shard_batched_query``): landmark planes over ``model``,
  the query batch over ``data``. The Eq.-3 min-contraction reduces over
  the sharded landmark axes through collectives (one `all_gather` of the
  target labels + one `pmin`); the bounded BiBFS runs all-local per query
  shard. Query batches are padded to the data-axis size and sliced back.

* **Cross-plane reductions** (``affected_vertices``): the per-plane `aff`
  planes OR-merge into one affected-vertex mask through a `pmax`.

Bit-parity: per-plane values are exact int32 fixpoints independent of
iteration count, and min/OR reductions are associative — sharded outputs
are bit-identical to the unsharded `vmap` path on any mesh shape
(`tests/test_shard.py` pins it on 1-device and forced-8-device meshes).

Sweep backends: both engine backends run *inside* the shard bodies. The
`RelaxPlan` rides into every `shard_map` as an ordinary replicated
argument (in_spec `P()` over its pytree leaves — the plan pytree may be
None, the tile-less jnp plan, or a full Pallas tiling), so each device
launches the tiled `edge_relax` kernel on its local planes; the
shard-aware tiling (`kernels/edge_relax`, leading vertex-shard axis on
`BlockedGraph`) is bit-identical for every shard count, and the tiling is
prepared once by the host-side `RelaxEngine` and reused by sharded and
unsharded call-sites alike (DESIGN.md §3–§4). With `use_kernel=True` the
query bound runs the `minplus` kernel per shard on its local highway rows
([P, R] rectangular contraction) and a `pmin` over the model axis
finishes the reduction — no [R, R] plane product is materialized.

Requirements: R must divide evenly over the plane-sharding axes (data ×
model for maintenance, model for queries). Query batches are padded
automatically; landmark counts are validated with a clear error.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.graphs.coo import (Graph, BatchUpdate, INF_D, apply_batch,
                              resolve_seed_weights)
from repro.core.batch import (check_labelling_width, repair_chunk_body,
                              repair_merge, repair_planes, repair_start_body,
                              search_basic_planes, search_basic_seed,
                              search_chunk_body, search_improved_planes,
                              search_improved_seed)
from repro.core.construct import construct_key2_planes
from repro.core.engine import RelaxPlan
from repro.core.labelling import (HighwayLabelling, key2_dist, key2_hub,
                                  key2_make, per_plane_hub_mask)
from repro.core.query import bounded_bibfs, effective_label_planes

#: Plane-sharding spec during maintenance: landmark planes over the whole
#: grid (`model` major, `data` minor — the data axis is idle while the
#: labelling is being rewritten, so it contributes landmark parallelism).
MAINT_AXES = ("model", "data")


def _check_planes(r: int, size: int, what: str) -> None:
    if r % size:
        raise ValueError(
            f"landmark count {r} must be divisible by the {what} "
            f"sharding size {size}; pick R as a multiple (or a smaller "
            f"--shards / mesh)")


def _maint_size(mesh) -> int:
    return mesh.shape["model"] * mesh.shape["data"]


def validate_landmark_sharding(mesh, r: int) -> None:
    """Pre-flight check of R against *both* plane groupings of a mesh.

    Maintenance shards landmark planes over data·model (the idle data
    axis donates its parallelism); queries regroup them over model only.
    Each failing grouping is named explicitly — `R % n_devices` alone
    can't tell a caller which phase's regrouping broke, and keeps working
    silently if the groupings ever diverge.
    """
    data, model = mesh.shape["data"], mesh.shape["model"]
    failing = []
    if r % (data * model):
        failing.append(f"maintenance grouping data×model = "
                       f"{data}×{model} = {data * model}")
    if r % model:
        failing.append(f"query grouping model = {model}")
    if failing:
        raise ValueError(
            f"landmark count R={r} must be divisible by every plane "
            f"grouping of the mesh; failing: {'; '.join(failing)} — pick "
            f"R as a multiple, or a smaller mesh / --shards")


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mesh", "max_iters"))
def shard_build_labelling(mesh, g: Graph, landmarks: jax.Array,
                          max_iters: int | None = None,
                          plan: RelaxPlan | None = None) -> HighwayLabelling:
    """`build_labelling` under shard_map; bit-identical outputs.

    Returns a labelling whose dist/hub planes are sharded over
    ``("model", "data")`` on the R axis and whose highway is row-sharded;
    consumers reshard transparently. `plan` (replicated into every shard)
    selects the sweep backend — Pallas plans launch the tiled kernel on
    each shard's local planes.
    """
    _check_planes(landmarks.shape[0], _maint_size(mesh), "maintenance")

    def body(g, own, landmarks_full, plan):
        key2 = construct_key2_planes(g, own, landmarks_full, max_iters, plan)
        dist = jnp.minimum(key2_dist(key2), INF_D)
        hub = key2_hub(key2) & (dist < INF_D)
        highway = dist[:, landmarks_full]    # local rows [P, R]
        return dist, hub, highway

    rv = P(MAINT_AXES, None)
    dist, hub, highway = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(MAINT_AXES), P(), P()),
        out_specs=(rv, rv, rv),
        # Every output is fully plane-sharded, so the varying-axis check
        # has nothing to prove; it stays off for the fixpoint sweeps.
        check_vma=False)(g, landmarks, landmarks, plan)
    return HighwayLabelling(landmarks.astype(jnp.int32), dist, hub, highway)


# ---------------------------------------------------------------------------
# Batch update
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mesh", "improved"))
def shard_batchhl_update(mesh, g_old: Graph, batch: BatchUpdate,
                         labelling: HighwayLabelling, improved: bool = True,
                         plan: RelaxPlan | None = None,
                         g_new: Graph | None = None
                         ) -> tuple[Graph, HighwayLabelling, jax.Array]:
    """`batchhl_update` under shard_map; bit-identical (G', Γ', aff).

    Per-plane search + repair run all-local on each shard's plane slice;
    the batch, both graph snapshots, and the plan are replicated. aff and
    the new planes come back sharded over ``("model", "data")`` on the R
    axis. Like `batchhl_update`, a Pallas `plan` must be prepared from the
    *post-update* snapshot; callers that already materialized it (for that
    prepare) can pass it as `g_new` to skip the recompute.
    """
    _check_planes(labelling.num_landmarks, _maint_size(mesh), "maintenance")
    # Trace-time growth guard: a grown graph with un-grown planes would
    # otherwise die as a GSPMD shape error inside the shard_map body.
    check_labelling_width(g_old, labelling.dist)
    if g_new is None:
        g_new = apply_batch(g_old, batch)
    # Same seed-weight contract as the unsharded batchhl_update: seeds
    # cross deletion/re-weight edges at their pre-update weight, resolved
    # against g_old; apply_batch above took the original batch.
    batch = resolve_seed_weights(g_old, batch)

    def body(g_new, batch, dist, hub, own, landmarks_full, plan):
        hub_mask = per_plane_hub_mask(landmarks_full, own, g_new.n)
        if improved:
            aff, _ = search_improved_planes(g_new, batch, dist, hub,
                                            hub_mask, plan)
        else:
            aff, _ = search_basic_planes(g_new, batch, dist, plan)
        new_key2, _ = repair_planes(g_new, aff, key2_make(dist, hub),
                                    hub_mask, plan)
        ndist = jnp.minimum(key2_dist(new_key2), INF_D)
        nhub = key2_hub(new_key2) & (ndist < INF_D)
        highway = ndist[:, landmarks_full]   # local rows [P, R]
        return ndist, nhub, highway, aff

    rv = P(MAINT_AXES, None)
    ndist, nhub, highway, aff = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), rv, rv, P(MAINT_AXES), P(), P()),
        out_specs=(rv, rv, rv, rv),
        # Outputs are fully plane-sharded; no varying-axis check needed.
        check_vma=False)(
            g_new, batch, labelling.dist, labelling.hub,
            labelling.landmarks, labelling.landmarks, plan)
    new_labelling = HighwayLabelling(labelling.landmarks, ndist, nhub,
                                     highway)
    return g_new, new_labelling, aff


@partial(jax.jit, static_argnames=("mesh",))
def affected_vertices(mesh, aff: jax.Array) -> jax.Array:
    """OR-merge the per-plane affected sets into one bool[V] vertex mask.

    The cross-plane reduction of DESIGN.md §4: each shard ORs its local
    planes, then a `pmax` over the plane-sharding axes merges the shards.
    """
    def body(aff_loc):
        any_loc = jnp.any(aff_loc, axis=0).astype(jnp.int32)
        return jax.lax.pmax(any_loc, MAINT_AXES) > 0

    return jax.shard_map(body, mesh=mesh,
                     in_specs=(P(MAINT_AXES, None),),
                     out_specs=P(None))(aff)


# ---------------------------------------------------------------------------
# Bounded update chunks (the serving pipeline's mesh path, DESIGN.md §5)
# ---------------------------------------------------------------------------
#
# `core/snapshot.pipelined_update` runs the batch update as bounded
# dispatches so query microbatches interleave on the device queue. These
# are the mesh twins of the unsharded chunk jits in `core/snapshot.py`:
# the same chunk bodies (`core/batch.py`), under shard_map on the
# maintenance plane grouping (landmark planes over ("model", "data")),
# with the graph, batch, and plan replicated. Each twin but the seed
# takes its unsharded program's arguments after `mesh`, so
# `pipelined_update` calls either set alike; the bodies build their
# sweep streams in every wave, so `streams` is None in and out. The
# frontier bitmap `front` [P, NBf] shards like the labelling planes
# (rv): each device propagates and relaxes the
# frontier of its own plane slice, and takes the masked/full density
# branch against it (a tighter mask than a global one, still exact per
# plane). The per-chunk `changed` flag is the one cross-shard reduction
# (a pmax OR-merge); everything else is all-local, exactly like the
# monolithic maintenance bodies.

def _any_shard(changed: jax.Array) -> jax.Array:
    """OR-merge a per-shard convergence flag over the maintenance grouping."""
    return jax.lax.pmax(changed.astype(jnp.int32), MAINT_AXES) > 0


@partial(jax.jit, static_argnames=("mesh", "improved"))
def shard_search_seed(mesh, g_new: Graph, batch: BatchUpdate,
                      dist: jax.Array, hub: jax.Array, landmarks: jax.Array,
                      improved: bool = True):
    """Mesh twin of `snapshot.search_seed`; outputs plane-sharded rv, and
    None for the streams."""
    _check_planes(landmarks.shape[0], _maint_size(mesh), "maintenance")
    check_labelling_width(g_new, dist)

    def body(g_new, batch, dist, hub, own, landmarks_full):
        hub_mask = per_plane_hub_mask(landmarks_full, own, g_new.n)
        if improved:
            seed, seeded, beta = search_improved_seed(g_new, batch, dist,
                                                      hub, hub_mask)
            return seed, seeded, beta, hub_mask
        seed, seeded = search_basic_seed(g_new, batch, dist)
        return seed, seeded, dist, hub_mask

    rv = P(MAINT_AXES, None)
    seed, seeded, bound, hub_mask = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), rv, rv, P(MAINT_AXES), P()),
        out_specs=(rv, rv, rv, rv),
        check_vma=False)(g_new, batch, dist, hub, landmarks, landmarks)
    return seed, seeded, bound, hub_mask, None


@partial(jax.jit, static_argnames=("mesh", "improved", "sweeps"))
def shard_search_chunk(mesh, g_new: Graph, best: jax.Array, seed: jax.Array,
                       bound: jax.Array, hub_mask: jax.Array,
                       plan: RelaxPlan | None, streams=None,
                       front: jax.Array | None = None, improved: bool = True,
                       sweeps: int = 1):
    """Mesh twin of `snapshot.search_chunk` →
    (best', changed scalar, front')."""

    def body(g_new, best, seed, bound, hub_mask, plan, front):
        cur, changed, front = search_chunk_body(
            g_new, best, seed, bound, hub_mask, plan, None, front, improved,
            sweeps)
        return cur, _any_shard(changed), front

    rv = P(MAINT_AXES, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), rv, rv, rv, rv, P(), rv),
        out_specs=(rv, P(), rv),
        check_vma=False)(g_new, best, seed, bound, hub_mask, plan, front)


@partial(jax.jit, static_argnames=("mesh",))
def shard_repair_start(mesh, g_new: Graph, aff: jax.Array, dist: jax.Array,
                       hub: jax.Array, hub_mask: jax.Array,
                       plan: RelaxPlan | None, streams=None):
    """Mesh twin of `snapshot.repair_start` (Algo-4 boundary seeding) →
    (base, None, front)."""

    def body(g_new, aff, dist, hub, hub_mask, plan):
        return repair_start_body(g_new, aff, dist, hub, hub_mask, plan, None)

    rv = P(MAINT_AXES, None)
    base, front = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), rv, rv, rv, rv, P()),
        out_specs=(rv, rv),
        check_vma=False)(g_new, aff, dist, hub, hub_mask, plan)
    return base, None, front


@partial(jax.jit, static_argnames=("mesh", "sweeps"))
def shard_repair_chunk(mesh, g_new: Graph, cur: jax.Array, aff: jax.Array,
                       hub_mask: jax.Array, plan: RelaxPlan | None,
                       streams=None, front: jax.Array | None = None,
                       sweeps: int = 1):
    """Mesh twin of `snapshot.repair_chunk` →
    (cur', changed scalar, front')."""

    def body(g_new, cur, aff, hub_mask, plan, front):
        out, changed, front = repair_chunk_body(g_new, cur, aff, hub_mask,
                                                plan, None, front, sweeps)
        return out, _any_shard(changed), front

    rv = P(MAINT_AXES, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), rv, rv, rv, P(), rv),
        out_specs=(rv, P(), rv),
        check_vma=False)(g_new, cur, aff, hub_mask, plan, front)


@partial(jax.jit, static_argnames=("mesh",))
def shard_update_finish(mesh, aff: jax.Array, settled: jax.Array,
                        dist: jax.Array, hub: jax.Array,
                        landmarks: jax.Array) -> HighwayLabelling:
    """Mesh twin of `snapshot.update_finish`; labelling comes back
    plane-sharded rv with row-sharded highway, like the monolithic
    `shard_batchhl_update`."""

    def body(aff, settled, dist, hub, landmarks_full):
        new_key2 = repair_merge(aff, settled, key2_make(dist, hub))
        ndist = jnp.minimum(key2_dist(new_key2), INF_D)
        nhub = key2_hub(new_key2) & (ndist < INF_D)
        highway = ndist[:, landmarks_full]   # local rows [P, R]
        return ndist, nhub, highway

    rv = P(MAINT_AXES, None)
    ndist, nhub, highway = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rv, rv, rv, rv, P()),
        out_specs=(rv, rv, rv),
        check_vma=False)(aff, settled, dist, hub, landmarks)
    return HighwayLabelling(landmarks.astype(jnp.int32), ndist, nhub,
                            highway)


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mesh", "max_steps", "use_kernel"))
def shard_batched_query(mesh, g: Graph, labelling: HighwayLabelling,
                        s: jax.Array, t: jax.Array, max_steps: int = 64,
                        use_kernel: bool = False,
                        plan: RelaxPlan | None = None) -> jax.Array:
    """`batched_query` under shard_map; bit-identical exact distances.

    Landmark planes shard over ``model``; the query batch shards over
    ``data`` (padded to a multiple of the data-axis size, sliced back).
    The Eq.-3 upper bound reduces over the sharded landmark axis with one
    `all_gather` (target labels) + one `pmin`; the BiBFS expands each
    query shard all-local against the replicated graph. Within a data
    shard the BiBFS batch composition differs from the unsharded run, but
    the returned min(d_sparse, d⊤) is composition-independent: BFS levels
    are exact, so d_sparse is exact whenever it undercuts d⊤ and is
    dominated by d⊤ otherwise. The padded path is locked in by the B=37
    sweep over data>1 meshes in `_selftest` below (run as
    tests/test_shard.py::test_multidevice_parity_selftest).
    """
    _check_planes(labelling.num_landmarks, mesh.shape["model"], "model")
    b = s.shape[0]
    pad = (-b) % mesh.shape["data"]
    if pad:
        s = jnp.concatenate([s, jnp.zeros((pad,), s.dtype)])
        t = jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])

    def body(g, dist, hub, own, landmarks_full, highway_rows, s, t, plan):
        # Eq. 3 — tropical contraction with the landmark axis sharded:
        # each shard contracts its local highway rows [P, R] against the
        # all-gathered target labels; a pmin over `model` finishes the
        # reduction. No [R, R] plane product is ever materialized.
        vals = effective_label_planes(dist, hub, own, landmarks_full)
        s_lab = jnp.minimum(vals[:, s].T, INF_D)      # [B_loc, P]
        t_lab = jnp.minimum(vals[:, t].T, INF_D)      # [B_loc, P]
        t_all = jax.lax.all_gather(t_lab, "model", axis=1, tiled=True)
        if use_kernel:
            # Per-shard minplus launch on the rectangular [P, R]
            # highway-row slice. Same auto-dispatch as the unsharded
            # query_upper_bound: the Pallas kernel on TPU, the jnp oracle
            # elsewhere — so --use-minplus-kernel costs the same with and
            # without a mesh (tests/test_shard_tiling.py pins the
            # interpret-mode kernel inside shard_map separately).
            from repro.kernels.minplus import ops as minplus_ops
            partial_bound = minplus_ops.minplus_bound(
                s_lab, highway_rows, t_all)
        else:
            # mid[b, j] = min over local i of s_lab[b, i] + H[i, j]
            mid = jnp.min(s_lab[:, :, None] + highway_rows[None, :, :],
                          axis=1)
            partial_bound = jnp.min(mid + t_all, axis=1)  # [B_loc]
        d_top = jnp.minimum(jax.lax.pmin(partial_bound, "model"), INF_D)

        # Bounded BiBFS on the local query shard (replicated over model).
        # The BiBFS's wave counters stay inside the shard_map: the mesh
        # path returns answers only.
        d_sparse, *_ = bounded_bibfs(g, landmarks_full, s, t, d_top,
                                     max_steps, plan)
        out = jnp.minimum(d_sparse, d_top)
        return jnp.where(out >= INF_D, INF_D, out)

    qv = P("model", None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), qv, qv, P("model"), P(), qv, P("data"), P("data"),
                  P()),
        out_specs=P("data"),
        # Replication over `model` holds by construction (all body inputs
        # are either replicated or pmin-merged before the BiBFS loop), so
        # the varying-axis check is off.
        check_vma=False)(
            g, labelling.dist, labelling.hub, labelling.landmarks,
            labelling.landmarks, labelling.highway, s, t, plan)[:b]


# ---------------------------------------------------------------------------
# Self-test (runnable under a forced multi-device host platform)
# ---------------------------------------------------------------------------

def _selftest() -> None:
    """Sharded-vs-unsharded bit-parity on every host-mesh factorization,
    on both sweep backends (jnp reference and the shard-aware Pallas
    tiling, incl. the per-shard minplus kernel bound).

    Run with a forced device count to exercise real multi-device meshes:

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            PYTHONPATH=src python -m repro.core.shard
    """
    import numpy as np
    from repro.graphs import generators as gen
    from repro.graphs.coo import apply_batch, from_edges, make_batch
    from repro.core.construct import build_labelling, \
        select_landmarks_by_degree
    from repro.core.batch import batchhl_update
    from repro.core.engine import RelaxEngine
    from repro.core.query import batched_query
    from repro.launch.mesh import make_host_mesh

    n_dev = len(jax.devices())
    n, r = 120, 8
    edges = gen.random_connected(n, extra_edges=150, seed=3)
    g = from_edges(n, edges, edges.shape[0] + 64)
    landmarks = select_landmarks_by_degree(g, r)
    ups = gen.random_batch_updates(edges, n, n_ins=6, n_del=6, seed=9)
    batch = make_batch(ups, pad_to=12)
    rng = np.random.default_rng(0)
    qs = jnp.asarray(rng.integers(0, n, 37), jnp.int32)   # odd B → padding
    qt = jnp.asarray(rng.integers(0, n, 37), jnp.int32)

    lab0 = build_labelling(g, landmarks)
    g1, lab1, aff1 = batchhl_update(g, batch, lab0, improved=True)
    d1 = batched_query(g1, lab1, qs, qt)

    # Shard-aware Pallas tiling (2 vertex shards): one plan per snapshot,
    # reused across every mesh factorization below.
    engine = RelaxEngine(backend="pallas", block_v=32, shards=2)
    plan0 = engine.prepare(g)
    g1_host = apply_batch(g, batch)
    engine1 = RelaxEngine(backend="pallas", block_v=32, shards=2)
    plan1 = engine1.prepare(g1_host)

    for model in [m for m in (1, 2, 4, 8) if n_dev % m == 0]:
        mesh = make_host_mesh(model=model)
        for backend, pln0, pln1 in (("jnp", None, None),
                                    ("pallas", plan0, plan1)):
            slab0 = shard_build_labelling(mesh, g, landmarks, plan=pln0)
            for f in ("dist", "hub", "highway"):
                np.testing.assert_array_equal(np.asarray(getattr(slab0, f)),
                                              np.asarray(getattr(lab0, f)))
            sg1, slab1, saff1 = shard_batchhl_update(mesh, g, batch, slab0,
                                                     plan=pln1)
            np.testing.assert_array_equal(np.asarray(saff1),
                                          np.asarray(aff1))
            for f in ("dist", "hub", "highway"):
                np.testing.assert_array_equal(np.asarray(getattr(slab1, f)),
                                              np.asarray(getattr(lab1, f)))
            sd1 = shard_batched_query(mesh, sg1, slab1, qs, qt,
                                      use_kernel=(backend == "pallas"),
                                      plan=pln1)
            np.testing.assert_array_equal(np.asarray(sd1), np.asarray(d1))
            affv = affected_vertices(mesh, saff1)
            np.testing.assert_array_equal(
                np.asarray(affv), np.asarray(jnp.any(aff1, axis=0)))
            print(f"mesh (data={mesh.shape['data']}, model={model}) "
                  f"backend={backend}: construction/update/query "
                  f"bit-parity OK")
    print(f"selftest OK on {n_dev} device(s)")


if __name__ == "__main__":
    _selftest()
