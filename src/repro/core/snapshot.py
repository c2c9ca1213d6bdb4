"""Versioned snapshots and the chunked-update serving pipeline.

The serving architecture of DESIGN.md §5: queries must stay fast *while*
the graph churns (the paper's premise), but a monolithic
`batchhl_update` is one device dispatch — on a single execution queue,
any query enqueued behind it waits for the whole update, so tail latency
is bounded below by update time. This module breaks that head-of-line
blocking with two pieces:

* **`Snapshot` / `SnapshotStore`** — an immutable serving unit
  (graph + labelling + prepared `RelaxPlan` + version id) behind a
  single-writer many-reader store. Queries always dispatch against the
  *committed* snapshot; an update builds snapshot N+1 off to the side
  and `commit` swaps the pointer atomically. JAX arrays are immutable,
  so in-flight queries against snapshot N stay valid across the swap —
  answers are always exact *at some committed version* (bounded
  staleness, never inconsistency).

* **`pipelined_update`** — the BatchHL update (batch search Algos 2–3 +
  batch repair Algo 4) as a generator of *bounded* device dispatches:
  seed, then fixpoint sweeps in chunks of `chunk_sweeps` waves, then
  repair likewise, then finalize. Each yield comes once the chunk has
  finished, and the caller interleaves query microbatches there;
  because each chunk is a fixed number of relaxation sweeps, a query
  waits at most for the chunk in flight (a few sweeps) instead of the
  full update. The chunk bodies are the
  *same* seed/step functions the monolithic fixpoints use
  (`core/batch.py`), and the fixpoint is monotone, so the committed
  labelling is bit-identical to `batchhl_update` — extra converged
  sweeps are no-ops (`tests/test_pipeline.py` pins it).

Under a mesh the chunks run through the `core/shard.py` wrappers with
the maintenance plane grouping (landmark planes over data×model) while
query microbatches keep the query grouping (planes over model, batch
over data) — the regrouping contract of DESIGN.md §4, now interleaved
on the same device queue instead of serialized.

Checkpointing: `save_snapshot` / `restore_snapshot` persist the *full*
serve state — graph topology (src/dst/valid), labelling, and version —
so a restarted loop resumes exactly (the `RelaxPlan` is derived state,
re-prepared by the engine on restore).
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.coo import (Graph, BatchUpdate, INF_D, apply_batch, grow,
                              resolve_seed_weights)
from repro.checkpoint import manager as ckpt
from repro.core.batch import (check_labelling_width, repair_chunk_body,
                              repair_merge, repair_start_body,
                              repair_streams, search_basic_seed,
                              search_chunk_body, search_improved_seed,
                              use_frontier)
from repro.core.engine import RelaxPlan, SweepStreams, sweep_streams
from repro.core.labelling import (HighwayLabelling, INF_KEY4,
                                  grow_labelling,
                                  key2_dist, key2_hub, key2_make,
                                  per_plane_hub_mask)


class UnweightedCheckpointError(FileNotFoundError):
    """A checkpoint from before the weighted-metric format (no graph_w).

    Named so callers can distinguish "old format" from "no checkpoint" /
    "corrupt shapes" — the weight column cannot be defaulted silently
    (w ≡ 1 would be a *guess* about the stream that produced the state).
    """


# ---------------------------------------------------------------------------
# Snapshot + store
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One immutable serving unit: everything a query needs, versioned.

    `plan` is the `RelaxPlan` prepared for this graph snapshot (None on
    the jnp backend); it rides along so queries at version N keep using
    N's tiling even while the engine prepares N+1's.
    """
    version: int
    graph: Graph
    labelling: HighwayLabelling
    plan: RelaxPlan | None = None


class SnapshotStore:
    """Single-writer / many-reader versioned snapshot pointer.

    Reads (`committed`) are one attribute load — atomic under the GIL, no
    lock on the query path. `commit` swaps the pointer and enforces
    contiguous versions, so "answered at version v" is always meaningful.
    """

    def __init__(self, snapshot: Snapshot):
        self._committed = snapshot

    @property
    def committed(self) -> Snapshot:
        return self._committed

    @property
    def version(self) -> int:
        return self._committed.version

    def commit(self, snapshot: Snapshot) -> Snapshot:
        if snapshot.version != self._committed.version + 1:
            raise ValueError(
                f"commit of version {snapshot.version} onto "
                f"{self._committed.version}: versions must be contiguous")
        self._committed = snapshot
        return snapshot


def grow_snapshot(snap: Snapshot, *, capacity: int | None = None,
                  n: int | None = None) -> Snapshot:
    """The grown twin of `snap`: same version, same logical graph, larger
    static slots (DESIGN.md §6).

    Growth is a pure shape change — every edge, distance, and hub flag is
    preserved, and new vertex columns are seeded exactly as a fresh
    construction at the larger size would leave an isolated vertex — so
    the grown snapshot keeps the *same* version: committing happens only
    when the next batch update lands (version + 1, at the grown shapes,
    through the store's pointer swap). Queries keep serving the committed
    pre-growth snapshot meanwhile, preserving the staleness ≤ 1 contract.
    `plan` is dropped: tilings are shape-keyed derived state, and the
    engine's fingerprint (which includes n and capacity) guarantees the
    re-prepare is a clean retile, never a stale-tile reuse.
    """
    g = grow(snap.graph, capacity=capacity, n=n)
    return Snapshot(snap.version, g, grow_labelling(snap.labelling, g.n),
                    None)


# ---------------------------------------------------------------------------
# Bounded update chunks (unsharded; core/shard.py holds the mesh twins)
# ---------------------------------------------------------------------------
#
# Each fixpoint phase's sweep streams (`engine.sweep_streams`: the tiled
# edge mask, weights and per-plane hub flags) are built once, in the
# phase's first dispatch, and passed to every later chunk of the phase:
# `search_seed` builds the search set, `repair_start` the repair set (the
# interior masks, over the search set's weights and hub flags). A chunk's
# wave then gathers only its source keys. `repair_start` takes the
# search set donated, so the hub flags it hands on keep their buffer (a
# copy would hold a third [R, S, NR, W] stream at once); callers rebind
# to the returned set.
#
# Frontier mode (DESIGN.md §10): the chunk programs take the changed-block
# bitmap `front` as an optional argument (None when `use_frontier(plan,
# g_new)` is false) and return it; `repair_start` derives it from its
# seeding. The bodies (`core/batch.py`) are shared with the mesh twins.

@partial(jax.jit, static_argnames=("improved",))
def search_seed(g_new: Graph, batch: BatchUpdate, dist: jax.Array,
                hub: jax.Array, landmarks: jax.Array,
                plan: RelaxPlan | None = None, improved: bool = True):
    """Batch-search initial state: (seed keys, seeded, bound, hub_mask,
    streams).

    `bound` is the per-vertex accept bound of the search step (β for the
    improved Algo 3, d_G for the basic Algo 2); `hub_mask` is reused by
    every later phase of the tick, `streams` by every search chunk and
    by `repair_start`.
    """
    check_labelling_width(g_new, dist)
    hub_mask = per_plane_hub_mask(landmarks, landmarks, g_new.n)
    streams = sweep_streams(plan, g_new, hub=hub_mask)
    if improved:
        seed, seeded, beta = search_improved_seed(g_new, batch, dist, hub,
                                                  hub_mask)
        return seed, seeded, beta, hub_mask, streams
    seed, seeded = search_basic_seed(g_new, batch, dist)
    return seed, seeded, dist, hub_mask, streams


@jax.jit
def frontier_seed_blocks(plan: RelaxPlan, seeded: jax.Array) -> jax.Array:
    """Initial changed-block bitmap: wave 0 'changed' the seeded vertices."""
    return plan.frontier.changed_blocks(seeded)


@partial(jax.jit, static_argnames=("improved", "sweeps"))
def search_chunk(g_new: Graph, best: jax.Array, seed: jax.Array,
                 bound: jax.Array, hub_mask: jax.Array,
                 plan: RelaxPlan | None, streams: SweepStreams,
                 front: jax.Array | None = None, improved: bool = True,
                 sweeps: int = 1):
    """`sweeps` search waves in one bounded dispatch →
    (best', changed, front')."""
    return search_chunk_body(g_new, best, seed, bound, hub_mask, plan,
                             streams, front, improved, sweeps)


@partial(jax.jit, static_argnames=("improved",))
def search_finish(best: jax.Array, seeded: jax.Array,
                  improved: bool = True) -> jax.Array:
    """Settled search keys → aff[P, V] (the CP/LD-affected supersets)."""
    inf = INF_KEY4 if improved else INF_D
    return seeded | (best < inf)


@partial(jax.jit, donate_argnames=("streams",))
def repair_start(g_new: Graph, aff: jax.Array, dist: jax.Array,
                 hub: jax.Array, hub_mask: jax.Array,
                 plan: RelaxPlan | None, streams: SweepStreams):
    """Algo-4 boundary seeding as one bounded dispatch → (base, the
    repair phase's streams, built from the search phase's `streams`,
    which are donated, front): `front` marks the blocks the seeding
    reached in frontier mode, and is None otherwise."""
    base, front = repair_start_body(g_new, aff, dist, hub, hub_mask, plan,
                                    streams)
    return base, repair_streams(plan, g_new, aff, streams), front


@partial(jax.jit, static_argnames=("sweeps",))
def repair_chunk(g_new: Graph, cur: jax.Array, aff: jax.Array,
                 hub_mask: jax.Array, plan: RelaxPlan | None,
                 streams: SweepStreams, front: jax.Array | None = None,
                 sweeps: int = 1):
    """`sweeps` interior repair waves in one bounded dispatch →
    (cur', changed, front')."""
    return repair_chunk_body(g_new, cur, aff, hub_mask, plan, streams,
                             front, sweeps)


@jax.jit
def update_finish(aff: jax.Array, settled: jax.Array, dist: jax.Array,
                  hub: jax.Array, landmarks: jax.Array) -> HighwayLabelling:
    """Merge repaired keys into the labelling (dist/hub/highway)."""
    new_key2 = repair_merge(aff, settled, key2_make(dist, hub))
    ndist = jnp.minimum(key2_dist(new_key2), INF_D)
    nhub = key2_hub(new_key2) & (ndist < INF_D)
    highway = ndist[:, landmarks]
    return HighwayLabelling(landmarks, ndist, nhub, highway)


def _phase_programs(mesh) -> tuple:
    """(seed, search chunk, repair start, repair chunk, finish): the
    unsharded programs, or their `core/shard.py` twins with `mesh`
    bound. The twins share the signatures (the seed's sweeps nothing, so
    it takes no plan); their bodies build the sweep streams in every
    wave, so they take and return None for them."""
    if mesh is None:
        return (search_seed, search_chunk, repair_start, repair_chunk,
                update_finish)
    from repro.core import shard

    def seed(g_new, batch, dist, hub, landmarks, plan, improved):
        return shard.shard_search_seed(mesh, g_new, batch, dist, hub,
                                       landmarks, improved=improved)
    return (seed,) + tuple(partial(fn, mesh) for fn in (
        shard.shard_search_chunk, shard.shard_repair_start,
        shard.shard_repair_chunk, shard.shard_update_finish))


# ---------------------------------------------------------------------------
# The pipelined update
# ---------------------------------------------------------------------------

def pipelined_update(snapshot: Snapshot, batch: BatchUpdate, *,
                     plan: RelaxPlan | None = None,
                     g_new: Graph | None = None, mesh=None,
                     improved: bool = True, chunk_sweeps: int = 1,
                     stats: dict | None = None):
    """BatchHL update against `snapshot` as a generator of bounded
    dispatches; returns (snapshot N+1, aff[R, V]) via StopIteration.

    Yields a phase tag once each chunk (`chunk_sweeps` relaxation waves)
    has finished on the device — the caller serves query microbatches
    against the committed snapshot at every yield, and each finds the
    device free: a query waits for the chunk in flight, never for one
    dispatched after it arrived. Like `batchhl_update`, a Pallas `plan`
    must be prepared from the post-update snapshot (pass the
    materialized graph as `g_new` to skip the recompute). With `mesh`, chunks run through
    the `core/shard.py` wrappers on the maintenance plane grouping.

    `stats`, when given, receives the counts `batchhl_update` gives:
    "update_waves", the waves each fixpoint ran (chunks × chunk_sweeps),
    and "stream_builds", the sweep-stream sets the phases built (0
    under a mesh, whose bodies build them per wave).

    Drive it to completion with `run_pipelined_update`, or manually:

        gen = pipelined_update(snap, batch, plan=plan)
        for _phase in gen:
            serve_pending_queries()      # interleaved work goes here
        # StopIteration.value is the (snapshot, aff) result
    """
    seed_fn, chunk_fn, rstart_fn, rchunk_fn, finish_fn = \
        _phase_programs(mesh)
    lab = snapshot.labelling
    if g_new is None:
        g_new = apply_batch(snapshot.graph, batch)
    # Seeds must cross deletion/re-weight edges at their pre-update weight
    # (see coo.resolve_seed_weights, a program of its own so that its
    # [U, E2] match fuses); apply_batch above already consumed the
    # original post-update weights.
    batch = resolve_seed_weights(snapshot.graph, batch)

    waves = {"search": 0, "repair": 0}
    seed, seeded, bound, hub_mask, streams = seed_fn(
        g_new, batch, lab.dist, lab.hub, lab.landmarks, plan,
        improved=improved)
    builds = int(streams is not None)
    # The changed-block bitmap (frontier mode) is carried like `best`/`cur`
    # and never surfaced to callers; None runs full waves.
    front = (frontier_seed_blocks(plan, seeded)
             if use_frontier(plan, g_new) else None)
    best, changed = seed, True
    jax.block_until_ready(best)
    yield "search-seed"
    while bool(changed):
        best, changed, front = chunk_fn(
            g_new, best, seed, bound, hub_mask, plan, streams, front,
            improved=improved, sweeps=chunk_sweeps)
        waves["search"] += chunk_sweeps
        jax.block_until_ready(best)
        yield "search"
    aff = search_finish(best, seeded, improved=improved)

    # The rebind of `streams` drops the search phase's set: only its
    # weights and hub flags live on, in the repair set.
    cur, streams, front = rstart_fn(g_new, aff, lab.dist, lab.hub,
                                    hub_mask, plan, streams)
    changed = True
    builds += streams is not None
    jax.block_until_ready(cur)
    yield "repair-seed"
    while bool(changed):
        cur, changed, front = rchunk_fn(g_new, cur, aff, hub_mask, plan,
                                        streams, front, sweeps=chunk_sweeps)
        waves["repair"] += chunk_sweeps
        jax.block_until_ready(cur)
        yield "repair"
    del streams

    new_lab = finish_fn(aff, cur, lab.dist, lab.hub, lab.landmarks)
    if stats is not None:
        stats.update(update_waves=waves, stream_builds=builds)
    return Snapshot(snapshot.version + 1, g_new, new_lab, plan), aff


def run_pipelined_update(gen) -> tuple[Snapshot, jax.Array]:
    """Drain a `pipelined_update` with no interleaved work.

    The synchronous-equivalence hook: tests drain the generator dry and
    compare the committed snapshot bit-for-bit against `batchhl_update`.
    """
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


# ---------------------------------------------------------------------------
# Full-state checkpointing (graph + labelling + version)
# ---------------------------------------------------------------------------

def snapshot_state(snap: Snapshot) -> dict:
    """The restartable serve state as a flat checkpoint tree.

    Includes the graph topology slots — a labelling alone cannot resume a
    serve loop (no edge set to apply the next batch to, no capacity). The
    `RelaxPlan` is derived state and deliberately excluded: the engine
    re-prepares it from the restored graph.
    """
    g, lab = snap.graph, snap.labelling
    return {
        "version": np.int64(snap.version),
        "n": np.int64(g.n),
        "graph_src": g.src, "graph_dst": g.dst, "graph_valid": g.valid,
        "graph_w": g.w,
        "landmarks": lab.landmarks, "dist": lab.dist, "hub": lab.hub,
        "highway": lab.highway,
    }


def save_snapshot(ckpt_dir: str, snap: Snapshot,
                  extra: dict | None = None) -> str:
    """Atomically persist the full serve state as step_<version>.

    `extra` adds caller-owned host state to the same atomic checkpoint
    (the serve loop stores its incremental edge list there — deletion
    sampling is edge-*order* dependent, so the order itself is state).
    """
    state = snapshot_state(snap)
    for k, v in (extra or {}).items():
        if k in state:
            raise ValueError(f"extra key {k!r} collides with snapshot state")
        state[k] = v
    return ckpt.save(ckpt_dir, snap.version, state)


def restore_extra(ckpt_dir: str, names: tuple[str, ...],
                  step: int | None = None) -> dict:
    """Load caller-owned `extra` leaves saved alongside a snapshot."""
    step = step if step is not None else ckpt.latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    return ckpt.load_leaves(ckpt_dir, step, names)


def publish_snapshot(ckpt_dir: str, snap: Snapshot,
                     extra: dict | None = None) -> str:
    """`save_snapshot` + flip the CURRENT pointer to it, durably.

    The replica updater's commit path (DESIGN.md §9): the step's leaves
    are fsync'd and renamed *before* the pointer flip, so a reader that
    observes the new CURRENT can always map the snapshot it names.
    """
    path = save_snapshot(ckpt_dir, snap, extra=extra)
    ckpt.publish(ckpt_dir, snap.version)
    return path


def restore_snapshot(ckpt_dir: str, step: int | None = None,
                     mmap: bool = False) -> Snapshot:
    """Rebuild a `Snapshot` from the newest (or given) checkpoint.

    Self-describing: shapes and the static vertex count come from the
    checkpoint itself, so no template tree is needed. The returned
    snapshot has `plan=None` — prepare one with the serving engine.

    `mmap=True` maps the arrays copy-free on the host (the replica
    readers' path — N readers of one published labelling share one
    page-cache copy); the device transfer, if any, is the backend's.
    """
    step = step if step is not None else ckpt.latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    d = ckpt.step_dir(ckpt_dir, step)

    core = ("graph_src", "graph_dst", "graph_valid", "graph_w", "n",
            "landmarks", "dist", "hub", "highway", "version")
    try:
        leaves = ckpt.load_leaves(ckpt_dir, step, core, mmap=mmap)
    except FileNotFoundError as e:
        missing = [k for k in ("graph_src", "graph_dst", "graph_valid")
                   if not os.path.exists(os.path.join(d, k + ".npy"))]
        if missing:
            raise FileNotFoundError(
                f"checkpoint {d} lacks graph state {missing}: it predates "
                "the full-state format and cannot resume a serve loop") \
                from e
        if not os.path.exists(os.path.join(d, "graph_w.npy")):
            raise UnweightedCheckpointError(
                f"checkpoint {d} lacks the edge-weight column graph_w: it "
                "predates the weighted-metric format. Re-serve from the "
                "original stream (or re-save the snapshot) to migrate; the "
                "weight column cannot be reconstructed from topology "
                "alone.") from e
        raise

    g = Graph(jnp.asarray(leaves["graph_src"]),
              jnp.asarray(leaves["graph_dst"]),
              jnp.asarray(leaves["graph_valid"]),
              jnp.asarray(leaves["graph_w"]), int(leaves["n"]))
    lab = HighwayLabelling(jnp.asarray(leaves["landmarks"]),
                           jnp.asarray(leaves["dist"]),
                           jnp.asarray(leaves["hub"]),
                           jnp.asarray(leaves["highway"]))
    return Snapshot(int(leaves["version"]), g, lab, None)


# ---------------------------------------------------------------------------
# Self-test (runnable under a forced multi-device host platform)
# ---------------------------------------------------------------------------

def _selftest() -> None:
    """Pipelined-vs-monolithic bit-parity on every host-mesh factorization
    × both sweep backends, then a pipelined ServeLoop whose every answer
    is re-derived synchronously at the version it was served.

    Run with a forced device count to exercise real multi-device meshes:

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            PYTHONPATH=src python -m repro.core.snapshot
    """
    from repro.graphs import generators as gen
    from repro.graphs.coo import from_edges, make_batch
    from repro.core.construct import build_labelling, \
        select_landmarks_by_degree
    from repro.core.batch import batchhl_update
    from repro.core.engine import RelaxEngine
    from repro.core.query import batched_query
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import ServeConfig, ServeLoop

    n_dev = len(jax.devices())
    n, r = 120, 8
    edges = gen.random_connected(n, extra_edges=150, seed=3)
    g = from_edges(n, edges, edges.shape[0] + 64)
    landmarks = select_landmarks_by_degree(g, r)
    lab0 = build_labelling(g, landmarks)
    ups = gen.random_batch_updates(edges, n, n_ins=6, n_del=6, seed=9)
    batch = make_batch(ups, pad_to=12)
    g1, lab1, aff1 = batchhl_update(g, batch, lab0, improved=True)

    g1_host = apply_batch(g, batch)
    engine = RelaxEngine(backend="pallas", block_v=32, shards=2)
    plan1 = engine.prepare(g1_host)

    for model in [m for m in (1, 2, 4, 8) if n_dev % m == 0]:
        mesh = make_host_mesh(model=model)
        for backend, pln in (("jnp", None), ("pallas", plan1)):
            snap = Snapshot(0, g, lab0, pln)
            nxt, aff = run_pipelined_update(pipelined_update(
                snap, batch, plan=pln, mesh=mesh, chunk_sweeps=2))
            np.testing.assert_array_equal(np.asarray(aff), np.asarray(aff1))
            for f in ("dist", "hub", "highway"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(nxt.labelling, f)),
                    np.asarray(getattr(lab1, f)))
            print(f"mesh (data={mesh.shape['data']}, model={model}) "
                  f"backend={backend}: pipelined update bit-parity OK")

    # End-to-end: pipelined serving on a real mesh (if the device count
    # allows a model axis), every answer checked at its served version.
    shards = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    for backend in ("jnp", "pallas"):
        cfg = ServeConfig(n=200, deg=3, landmarks=8, batches=2,
                          batch_size=20, queries=24, qps=5000.0,
                          microbatch=8, pipeline=True, backend=backend,
                          block_v=64, tile_shards=2, mesh="host",
                          shards=shards, quiet=True, keep_history=True)
        rep = ServeLoop(cfg).run()
        for m in rep.microbatches:
            s = rep.history[m.version]
            want = batched_query(s.graph, s.labelling,
                                 jnp.asarray(m.qs), jnp.asarray(m.qt))
            np.testing.assert_array_equal(m.answers, np.asarray(want))
        assert any(m.staleness == 1 for m in rep.microbatches), \
            "no query overlapped an update — pipeline never engaged"
        print(f"serve pipeline backend={backend} (mesh shards={shards}): "
              f"{len(rep.microbatches)} microbatches exact at their "
              f"versions")
    print(f"pipeline selftest OK on {n_dev} device(s)")


if __name__ == "__main__":
    _selftest()
