"""BatchHL: batch search (Algorithms 2 & 3) and batch repair (Algorithm 4).

TPU adaptation: the paper's priority-queue best-first searches become
monotone fixpoints of dense edge-relaxation sweeps (see DESIGN.md §2).
Because every expansion step adds exactly one hop, the queue is monotone and
its pop order is immaterial to the final key of each vertex — the sweep
fixpoint equals the queue result. All landmark planes run vmapped in
lockstep (the paper's landmark parallelism, §6) and the vertex axis is
shardable across the mesh `data` axis.

Every sweep routes through the relaxation engine (`core/engine.py`,
DESIGN.md §3): pass a `RelaxPlan` (from `RelaxEngine.prepare`) to run the
tiled Pallas `edge_relax` kernel; the default `plan=None` runs the pure-jnp
segment-min reference — both backends produce identical results.

Variants (paper §7 naming):
  BHL   = basic batch search (Algo 2) + batch repair (Algo 4)
  BHL+  = improved batch search (Algo 3) + batch repair (Algo 4)
  BHL^s = split insert/delete sub-batches (for Fig. 2 comparisons)
  UHL+  = unit-update loop (single-update baseline)
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.coo import (Graph, BatchUpdate, INF_D, apply_batch,
                              resolve_seed_weights)
from repro.core.engine import (RelaxEngine, RelaxPlan, SweepStreams,
                               gather_rows, relax_rows, relax_sweep,
                               sweep_streams)
from repro.core.labelling import (
    HighwayLabelling, INF_KEY2, INF_KEY4,
    key2_dist, key2_hub, key2_make,
    key4_from_key2, key4_extend, key4_beta,
    per_plane_hub_mask,
)

_MAX_WAVES_CAP = 1 << 20  # safety valve; loops exit on fixpoint far earlier


def check_labelling_width(g: Graph, dist: jax.Array) -> None:
    """Trace-time guard: the labelling planes must span exactly g.n.

    Grow-in-place (DESIGN.md §6) resizes the graph and the labelling
    together at a version boundary; a caller that grows one without the
    other would otherwise surface as an opaque gather/broadcast shape
    error from deep inside the jitted fixpoints. Shapes are static, so
    this costs nothing at runtime.
    """
    if dist.shape[1] != g.n:
        raise ValueError(
            f"labelling planes span {dist.shape[1]} vertices but the graph "
            f"has n={g.n}; grow them together (core/growth.ensure_capacity, "
            f"or coo.grow + labelling.grow_labelling) before updating")


def _per_plane_hub_mask(labelling: HighwayLabelling, n: int) -> jax.Array:
    """[R, V] hub mask over the full plane set of a labelling."""
    return per_plane_hub_mask(labelling.landmarks, labelling.landmarks, n)


def _fixpoint(body_fn, init: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Iterate x <- body_fn(x) (monotone, elementwise) until unchanged →
    (fixpoint, waves): the waves run, the last of them the one that
    changed nothing."""
    def cond(state):
        _, changed, it = state
        return changed & (it < _MAX_WAVES_CAP)

    def body(state):
        x, _, it = state
        nx = body_fn(x)
        return nx, jnp.any(nx != x), it + 1

    out, _, waves = jax.lax.while_loop(
        cond, body, (init, jnp.asarray(True), jnp.asarray(0)))
    return out, waves


def _over_planes(one, streams: SweepStreams | None, *planes: jax.Array):
    """`one(*plane_slices, streams_p)` vmapped over the landmark planes,
    the phase's `streams` split by `SweepStreams.plane_axes`."""
    axes = None if streams is None else streams.plane_axes()
    return jax.vmap(one, in_axes=(0,) * len(planes) + (axes,))(*planes,
                                                               streams)


# ---------------------------------------------------------------------------
# Frontier-proportional waves (change propagation, DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# Every fixpoint below is a monotone Bellman-Ford-style iteration, so a
# vertex can improve at wave k only through an edge whose source changed
# at wave k-1 (an unchanged finite source re-proposes the candidate the
# destination already absorbed — the per-destination acceptance bounds are
# wave-invariant, so the filtered candidate is unchanged too). Tracking
# *changed destination blocks* per plane and relaxing only the tile rows
# one block-adjacency hop ahead of them is therefore exact, not a
# heuristic: the masked wave computes bit-identical planes to the full
# sweep. When the frontier densifies past the plan's static row budget
# (`FrontierTiles.rows_cap`, the autotunable density threshold) the wave
# falls back to the full sweep — a *correctness* requirement, since a
# truncated `nonzero(size=...)` would silently drop active rows — and the
# frontier keeps being tracked so later sparse waves re-enter the masked
# mode. The branch is a scalar `lax.cond` with the plane vmap *inside*
# each branch: a per-plane cond under vmap would lower to `select` and
# execute both branches every wave.

def frontier_active_rows(plan: RelaxPlan, front: jax.Array
                         ) -> tuple[jax.Array, jax.Array]:
    """(active-row flags [NR], count) one propagation hop ahead of the
    changed-block bitmap `front` [P, NBf]."""
    ft = plan.frontier
    rows = ft.active_rows(ft.propagate(jnp.any(front, axis=0)))
    return rows, jnp.sum(rows)


def frontier_wave(plan: RelaxPlan, g: Graph, full_step, masked_step,
                  x: jax.Array, front: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """One frontier wave: propagate, relax (masked or full), re-derive.

    `full_step(x)` is the existing whole-plane wave; `masked_step(x,
    rows_g)` the same wave restricted to the gathered rows (`rows_g`
    from `engine.gather_rows`, shared across planes). Returns (x',
    front') where front' marks the blocks whose values changed — the
    fixpoint is reached exactly when front' is empty, matching
    `_fixpoint`'s x' == x test.
    """
    ft = plan.frontier
    rows, count = frontier_active_rows(plan, front)

    def masked(x):
        ridx = jnp.nonzero(rows, size=ft.rows_cap,
                           fill_value=ft.nrows)[0].astype(jnp.int32)
        return masked_step(x, gather_rows(plan, g, ridx))

    nx = jax.lax.cond(count <= ft.rows_cap, masked, full_step, x)
    return nx, ft.changed_blocks(nx != x)


def _frontier_fixpoint(plan: RelaxPlan, g: Graph, full_step, masked_step,
                       init: jax.Array, front0: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """Iterate `frontier_wave` until the changed-block frontier empties
    → (fixpoint, waves)."""
    def cond(state):
        _, front, it = state
        return jnp.any(front) & (it < _MAX_WAVES_CAP)

    def body(state):
        x, front, it = state
        nx, nfront = frontier_wave(plan, g, full_step, masked_step, x, front)
        return nx, nfront, it + 1

    out, _, waves = jax.lax.while_loop(cond, body,
                                       (init, front0, jnp.asarray(0)))
    return out, waves


def search_step_rows(rows_g, best: jax.Array, bound_g: jax.Array,
                     hub_mask: jax.Array | None, *,
                     improved: bool) -> jax.Array:
    """Masked twin of `search_{basic,improved}_step` over gathered rows.

    The full step's trailing `min(·, seed)` is dropped: the fixpoint
    starts at `best = seed` and is monotone decreasing, so the seed term
    is a no-op on every wave. The acceptance filter (Algo 2 line 12 /
    Algo 3 line 14) moves per-edge via `relax_rows(bound=...)`.
    """
    src_g, dstg, valid_g, w_g = rows_g
    if improved:
        def one(best_p, beta_p, hub_p):
            return relax_rows(best_p, best_p, src_g, dstg, valid_g, w_g,
                              4, INF_KEY4, hub=hub_p, clear_bit=2,
                              bound=beta_p)
        return jax.vmap(one)(best, bound_g, hub_mask)

    def one(best_p, dist_p):
        return relax_rows(best_p, best_p, src_g, dstg, valid_g, w_g,
                          1, INF_D, bound=dist_p)
    return jax.vmap(one)(best, bound_g)


def repair_step_rows(rows_g, cur: jax.Array, aff: jax.Array,
                     hub_mask: jax.Array) -> jax.Array:
    """Masked twin of `repair_step`: interior relaxation over gathered rows."""
    src_g, dstg, valid_g, w_g = rows_g

    def one(cur_p, aff_p, hub_p):
        emask = valid_g & aff_p[src_g] & aff_p[dstg]
        return relax_rows(cur_p, cur_p, src_g, dstg, emask, w_g,
                          2, INF_KEY2, hub=hub_p, clear_bit=1)
    return jax.vmap(one)(cur, aff, hub_mask)


def use_frontier(plan: RelaxPlan | None, g: Graph) -> bool:
    """Trace-time frontier dispatch: plan carries the tiling and the graph
    has edge slots (a zero-capacity snapshot has nothing to gather)."""
    return (plan is not None and plan.frontier is not None
            and g.src.shape[0] > 0)


# ---------------------------------------------------------------------------
# Batch Search — Algorithm 2 (basic, returns CP-affected superset)
# ---------------------------------------------------------------------------
#
# Each search below is decomposed into a *seed* (scatter the batch's anchor
# keys into per-plane planes) and a *step* (one relaxation wave over all
# planes). The monotone fixpoint of the step from the seed is the search
# result; the monolithic `*_planes` functions iterate it to convergence in
# one `while_loop`, and the serving pipeline (`core/snapshot.py`) iterates
# the *same* step in bounded chunks so query microbatches can interleave on
# the device queue — bit-identical by monotonicity (extra converged waves
# are no-ops).

def search_basic_seed(g_new: Graph, batch: BatchUpdate, dist_g: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Algo-2 seeds for a plane slice: (seed keys [P, V], seeded [P, V])."""
    n = g_new.n

    da = dist_g[:, batch.src]                                 # [P, U]
    db = dist_g[:, batch.dst]
    nontrivial = (da != db) & batch.valid[None, :]
    anchor = jnp.where(da < db, batch.dst[None, :], batch.src[None, :])
    d_pre = jnp.minimum(da, db)
    # Weighted seed: the anchor's candidate distance crosses the update's
    # edge at its seed weight (coo.resolve_seed_weights picks the superset-
    # safe one per op). No wrap guard needed: d_pre ≤ INF_D and w ≤ INF_D
    # keep the sum well under int32 max.
    seed_d = jnp.minimum(d_pre + batch.w[None, :], INF_D)
    seed_d = jnp.where(nontrivial, seed_d, INF_D)

    # Scatter-min seeds into per-plane planes.
    def scatter_seeds(anchors, vals):
        plane = jnp.full((n,), INF_D, jnp.int32)
        return plane.at[anchors].min(vals)
    seed = jax.vmap(scatter_seeds)(anchor, seed_d)            # [P, V]
    return seed, seed < INF_D                                 # anchors join
                                                              # V_AFF+ uncond.


def search_basic_step(plan: RelaxPlan | None, g_new: Graph, best: jax.Array,
                      seed: jax.Array, dist_g: jax.Array,
                      streams: SweepStreams | None = None) -> jax.Array:
    """One Algo-2 relaxation wave over all planes of a slice [P, V].

    `streams` are the search phase's (`engine.sweep_streams`; None builds
    them in the wave); Algo 2 clears no hub bit, so their hub flags are
    left out."""
    if streams is not None:
        streams = dataclasses.replace(streams, hub=None)

    def one(best_p, seed_p, dist_p, streams_p):
        cand = relax_sweep(plan, g_new, best_p, 1, INF_D, streams=streams_p)
        accept = cand <= dist_p                               # Algo2 line 12
        cand = jnp.where(accept, cand, INF_D)
        return jnp.minimum(best_p, jnp.minimum(cand, seed_p))
    return _over_planes(one, streams, best, seed, dist_g)


def search_basic_planes(g_new: Graph, batch: BatchUpdate, dist_g: jax.Array,
                        plan: RelaxPlan | None = None,
                        streams: SweepStreams | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Algo-2 search over an arbitrary plane slice `dist_g` [P, V] →
    (aff, waves).

    Entirely per-plane (the paper's landmark parallelism): `core/shard.py`
    runs this on each shard's local planes with no cross-shard traffic.
    """
    seed, seeded = search_basic_seed(g_new, batch, dist_g)
    step = lambda b: search_basic_step(plan, g_new, b, seed, dist_g, streams)
    if use_frontier(plan, g_new):
        best, waves = _frontier_fixpoint(
            plan, g_new, step,
            lambda b, rows_g: search_step_rows(rows_g, b, dist_g, None,
                                               improved=False),
            seed, plan.frontier.changed_blocks(seeded))
    else:
        best, waves = _fixpoint(step, seed)
    return seeded | (best < INF_D), waves


def batch_search_basic(g_old: Graph, g_new: Graph, batch: BatchUpdate,
                       labelling: HighwayLabelling,
                       plan: RelaxPlan | None = None) -> jax.Array:
    """Returns aff[R, V] bool — the CP-affected supersets, per landmark."""
    return search_basic_planes(g_new, batch, labelling.dist, plan)[0]


# ---------------------------------------------------------------------------
# Batch Search — Algorithm 3 (improved, extended landmark lengths)
# ---------------------------------------------------------------------------

def search_improved_seed(g_new: Graph, batch: BatchUpdate,
                         dist_g: jax.Array, hub_g: jax.Array,
                         hub_mask: jax.Array
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Algo-3 seeds for a plane slice: (seed key4 [P, V], seeded, beta)."""
    n = g_new.n
    key2_g = key2_make(dist_g, hub_g)                         # [P, V]
    beta = key4_beta(key2_g)                                  # [P, V]

    da = dist_g[:, batch.src]
    db = dist_g[:, batch.dst]
    nontrivial = (da != db) & batch.valid[None, :]
    a_is_pre = da < db
    anchor = jnp.where(a_is_pre, batch.dst[None, :], batch.src[None, :])
    pre = jnp.where(a_is_pre, batch.src[None, :], batch.dst[None, :])

    key2_pre = jnp.take_along_axis(key2_g, pre, axis=1)       # [P, U]
    # Re-weights take the deletion-flavoured e-flag: like deletions they
    # can lengthen existing shortest paths, and e=True yields the smaller
    # (more inclusive) key4 — the superset-safe choice.
    k4 = key4_from_key2(key2_pre, (batch.is_del | batch.is_rew)[None, :])
    anchor_is_hub = jnp.take_along_axis(hub_mask, anchor, axis=1)
    seed_k4 = key4_extend(k4, anchor_is_hub, w=batch.w[None, :])
    seed_k4 = jnp.where(nontrivial, seed_k4, INF_KEY4)

    def scatter_seeds(anchors, vals):
        plane = jnp.full((n,), INF_KEY4, jnp.int32)
        return plane.at[anchors].min(vals)
    seed = jax.vmap(scatter_seeds)(anchor, seed_k4)
    return seed, seed < INF_KEY4, beta


def search_improved_step(plan: RelaxPlan | None, g_new: Graph,
                         best: jax.Array, seed: jax.Array, beta: jax.Array,
                         hub_mask: jax.Array,
                         streams: SweepStreams | None = None) -> jax.Array:
    """One Algo-3 relaxation wave over all planes of a slice [P, V].

    `streams` are the search phase's, hub flags from `hub_mask` (None
    builds them in the wave)."""
    def one(best_p, seed_p, beta_p, hub_p, streams_p):
        # key4_extend per edge: +4, clamp, clear the l-bit at hub dsts.
        cand = relax_sweep(plan, g_new, best_p, 4, INF_KEY4,
                           hub=hub_p, clear_bit=2, streams=streams_p)
        accept = cand <= beta_p                               # Algo3 line 14
        cand = jnp.where(accept, cand, INF_KEY4)
        return jnp.minimum(best_p, jnp.minimum(cand, seed_p))
    return _over_planes(one, streams, best, seed, beta, hub_mask)


def search_improved_planes(g_new: Graph, batch: BatchUpdate,
                           dist_g: jax.Array, hub_g: jax.Array,
                           hub_mask: jax.Array,
                           plan: RelaxPlan | None = None,
                           streams: SweepStreams | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """Algo-3 search over an arbitrary plane slice (dist/hub/hub_mask
    [P, V]) → (aff, waves).

    Entirely per-plane; `core/shard.py` runs it on shard-local planes.
    """
    seed, seeded, beta = search_improved_seed(g_new, batch, dist_g, hub_g,
                                              hub_mask)
    step = lambda b: search_improved_step(plan, g_new, b, seed, beta,
                                          hub_mask, streams)
    if use_frontier(plan, g_new):
        best, waves = _frontier_fixpoint(
            plan, g_new, step,
            lambda b, rows_g: search_step_rows(rows_g, b, beta, hub_mask,
                                               improved=True),
            seed, plan.frontier.changed_blocks(seeded))
    else:
        best, waves = _fixpoint(step, seed)
    return seeded | (best < INF_KEY4), waves


def batch_search_improved(g_old: Graph, g_new: Graph, batch: BatchUpdate,
                          labelling: HighwayLabelling,
                          plan: RelaxPlan | None = None) -> jax.Array:
    """Returns aff[R, V] bool ⊇ LD-affected vertices, per landmark."""
    hub_mask = _per_plane_hub_mask(labelling, g_new.n)
    return search_improved_planes(g_new, batch, labelling.dist, labelling.hub,
                                  hub_mask, plan)[0]


# ---------------------------------------------------------------------------
# Batch Repair — Algorithm 4
# ---------------------------------------------------------------------------

def repair_base(plan: RelaxPlan | None, g_new: Graph, aff: jax.Array,
                key2_g: jax.Array, hub_mask: jax.Array,
                streams: SweepStreams | None = None) -> jax.Array:
    """Algo-4 boundary seeds: landmark-distance bounds from *unaffected*
    neighbours (line 3), INF_KEY2 off the affected sets. [P, V].

    One sweep: it takes the weight and hub streams of `streams` (the
    search phase's; None builds them) and builds its boundary mask in
    the sweep."""
    def one(aff_p, key2_p, hub_p, streams_p):
        bou_mask = g_new.valid & ~aff_p[g_new.src] & aff_p[g_new.dst]
        base = relax_sweep(plan, g_new, key2_p, 2, INF_KEY2,
                           hub=hub_p, clear_bit=1, edge_mask=bou_mask,
                           streams=streams_p)
        return jnp.where(aff_p, base, INF_KEY2)
    return _over_planes(one, streams, aff, key2_g, hub_mask)


def repair_base_frontier(plan: RelaxPlan, g_new: Graph, aff: jax.Array,
                         key2_g: jax.Array, hub_mask: jax.Array,
                         streams: SweepStreams | None = None
                         ) -> jax.Array:
    """Masked `repair_base`: one sweep over the affected sets' blocks.

    Boundary edges end on affected vertices, so the rows of the blocks
    holding *any* plane's affected vertices cover every boundary edge of
    every plane — no propagation hop needed. Falls back to the full
    sweep (on `streams`) when the affected footprint overflows the row
    budget.
    """
    ft = plan.frontier
    rows = ft.active_rows(ft.changed_blocks(jnp.any(aff, axis=0)))

    def masked(args):
        aff, key2_g, hub_mask = args
        ridx = jnp.nonzero(rows, size=ft.rows_cap,
                           fill_value=ft.nrows)[0].astype(jnp.int32)
        src_g, dstg, valid_g, w_g = gather_rows(plan, g_new, ridx)

        def one(aff_p, key2_p, hub_p):
            emask = valid_g & ~aff_p[src_g] & aff_p[dstg]
            base = relax_rows(key2_p, jnp.full_like(key2_p, INF_KEY2),
                              src_g, dstg, emask, w_g, 2, INF_KEY2,
                              hub=hub_p, clear_bit=1)
            return jnp.where(aff_p, base, INF_KEY2)
        return jax.vmap(one)(aff, key2_g, hub_mask)

    def full(args):
        aff, key2_g, hub_mask = args
        return repair_base(plan, g_new, aff, key2_g, hub_mask, streams)

    return jax.lax.cond(jnp.sum(rows) <= ft.rows_cap, masked, full,
                        (aff, key2_g, hub_mask))


def repair_streams(plan: RelaxPlan | None, g_new: Graph, aff: jax.Array,
                   streams: SweepStreams) -> SweepStreams:
    """The repair fixpoint's streams: each plane's interior mask (both
    ends affected, lines 5-15), built once for the phase, over the
    weight and hub streams of the search phase's `streams`."""
    int_mask = g_new.valid & aff[:, g_new.src] & aff[:, g_new.dst]
    return dataclasses.replace(
        streams, mask=sweep_streams(plan, g_new, int_mask).mask,
        plane_mask=True)


def repair_step(plan: RelaxPlan | None, g_new: Graph, cur: jax.Array,
                aff: jax.Array, hub_mask: jax.Array,
                streams: SweepStreams | None = None) -> jax.Array:
    """One Algo-4 interior relaxation wave (lines 5-15) over a slice.

    `streams` are `repair_streams`; None builds the interior masks (and
    the rest) in the wave."""
    def one(cur_p, aff_p, hub_p, streams_p):
        int_mask = (g_new.valid & aff_p[g_new.src] & aff_p[g_new.dst]
                    if streams_p is None else None)
        cand = relax_sweep(plan, g_new, cur_p, 2, INF_KEY2,
                           hub=hub_p, clear_bit=1, edge_mask=int_mask,
                           streams=streams_p)
        return jnp.minimum(cur_p, cand)
    return _over_planes(one, streams, cur, aff, hub_mask)


def repair_merge(aff: jax.Array, settled: jax.Array,
                 key2_g: jax.Array) -> jax.Array:
    """Rewrite only affected entries; unaffected labels are untouched."""
    return jnp.where(aff, settled, key2_g)


def repair_planes(g_new: Graph, aff: jax.Array, key2_g: jax.Array,
                  hub_mask: jax.Array,
                  plan: RelaxPlan | None = None,
                  streams: SweepStreams | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Algo-4 repair over an arbitrary plane slice → (new key2 [P, V],
    waves).

    The paper's ascending-distance wavefront (settle V_min, relax neighbors)
    is realized as a boundary-seeded relaxation fixpoint: identical final
    values by Lemma 5.20 + monotonicity. Entirely per-plane, so
    `core/shard.py` runs it on shard-local planes. `streams` are the
    search phase's; the fixpoint's own are built from them once
    (`repair_streams`). With None every wave builds its own.
    """
    rstreams = (None if streams is None
                else repair_streams(plan, g_new, aff, streams))
    step = lambda c: repair_step(plan, g_new, c, aff, hub_mask, rstreams)
    if use_frontier(plan, g_new):
        base = repair_base_frontier(plan, g_new, aff, key2_g, hub_mask,
                                    streams)
        settled, waves = _frontier_fixpoint(
            plan, g_new, step,
            lambda c, rows_g: repair_step_rows(rows_g, c, aff, hub_mask),
            base, plan.frontier.changed_blocks(base < INF_KEY2))
    else:
        base = repair_base(plan, g_new, aff, key2_g, hub_mask, streams)
        settled, waves = _fixpoint(step, base)
    return repair_merge(aff, settled, key2_g), waves


def relabel(labelling: HighwayLabelling, new_key2: jax.Array
            ) -> HighwayLabelling:
    """The labelling whose planes the repaired key2 planes encode."""
    dist = jnp.minimum(key2_dist(new_key2), INF_D)
    hub = key2_hub(new_key2) & (dist < INF_D)
    highway = dist[jnp.arange(labelling.num_landmarks)[:, None],
                   labelling.landmarks[None, :]]
    return HighwayLabelling(labelling.landmarks, dist, hub, highway)


def batch_repair(g_new: Graph, aff: jax.Array,
                 labelling: HighwayLabelling,
                 plan: RelaxPlan | None = None) -> HighwayLabelling:
    """Settle d^L_{G'} on the affected sets and rewrite labels minimally."""
    hub_mask = _per_plane_hub_mask(labelling, g_new.n)
    new_key2, _ = repair_planes(g_new, aff, labelling.key2(), hub_mask, plan)
    return relabel(labelling, new_key2)


# ---------------------------------------------------------------------------
# Bounded chunks (the serving pipeline's phase bodies, DESIGN.md §5)
# ---------------------------------------------------------------------------
#
# `core/snapshot.py` runs the fixpoints above as bounded dispatches of
# `sweeps` waves each, and `core/shard.py` runs the same bodies under
# shard_map. Frontier mode (DESIGN.md §10): the per-plane changed-block
# bitmap `front` [P, NBf] is chunk-carried state like the plane, None
# when `use_frontier` is false; the waves are chosen at trace time on
# it. The convergence flag is "is the frontier empty" with a bitmap and
# "did any key change" without: the same fixpoint condition, values
# bit-identical either way (the parity suites pin it).

def chunk_waves(plan, g_new, full_step, masked_step, x, front, sweeps):
    """`sweeps` waves from `x` → (x', changed, front').

    Without a bitmap (`front` None) each wave is `full_step`; with one
    each is a `frontier_wave` of `full_step`/`masked_step`."""
    out = x
    for _ in range(sweeps):
        if front is None:
            out = full_step(out)
        else:
            out, front = frontier_wave(plan, g_new, full_step, masked_step,
                                       out, front)
    changed = jnp.any(out != x) if front is None else jnp.any(front)
    return out, changed, front


def search_chunk_body(g_new, best, seed, bound, hub_mask, plan, streams,
                      front, improved, sweeps):
    """`sweeps` search waves (Algo 3, or Algo 2) from `best` →
    (best', changed, front')."""
    def full(b):
        if improved:
            return search_improved_step(plan, g_new, b, seed, bound,
                                        hub_mask, streams)
        return search_basic_step(plan, g_new, b, seed, bound, streams)

    def masked(b, rows_g):
        return search_step_rows(rows_g, b, bound,
                                hub_mask if improved else None,
                                improved=improved)
    return chunk_waves(plan, g_new, full, masked, best, front, sweeps)


def repair_start_body(g_new, aff, dist, hub, hub_mask, plan, streams):
    """Algo-4 boundary seeding → (base, front): in frontier mode masked
    to the affected blocks, with `front` the blocks `base` seeded; else
    a full sweep and None."""
    if not use_frontier(plan, g_new):
        return repair_base(plan, g_new, aff, key2_make(dist, hub), hub_mask,
                           streams), None
    base = repair_base_frontier(plan, g_new, aff, key2_make(dist, hub),
                                hub_mask, streams)
    return base, plan.frontier.changed_blocks(base < INF_KEY2)


def repair_chunk_body(g_new, cur, aff, hub_mask, plan, streams, front,
                      sweeps):
    """`sweeps` interior repair waves from `cur` → (cur', changed,
    front')."""
    return chunk_waves(
        plan, g_new,
        lambda c: repair_step(plan, g_new, c, aff, hub_mask, streams),
        lambda c, rows_g: repair_step_rows(rows_g, c, aff, hub_mask),
        cur, front, sweeps)


# ---------------------------------------------------------------------------
# BatchHL — Algorithm 1
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("improved",))
def batchhl_update_counted(g_old: Graph, batch: BatchUpdate,
                           labelling: HighwayLabelling, improved: bool = True,
                           plan: RelaxPlan | None = None,
                           g_new: Graph | None = None):
    """`batchhl_update` as one program that also counts →
    (G', Γ', aff, counts).

    `counts` is {"update_waves": {"search": n, "repair": n}, the two
    fixpoints' trip counts, "stream_builds": 2}: the search phase's
    sweep streams (validity, weights, hub flags) are built once, before
    its fixpoint, and the repair phase's interior masks once, before
    its own; `repair_base` reuses the search set's weights and hub flags.
    """
    check_labelling_width(g_old, labelling.dist)
    if g_new is None:
        g_new = apply_batch(g_old, batch)
    # Seeds for deletions / re-weights must cross the edge at its
    # pre-update weight (resp. min of old/new) — resolved against g_old;
    # apply_batch above takes the *original* batch (post-update weights).
    batch = resolve_seed_weights(g_old, batch)
    hub_mask = _per_plane_hub_mask(labelling, g_new.n)
    streams = sweep_streams(plan, g_new, hub=hub_mask)
    if improved:
        aff, search_waves = search_improved_planes(
            g_new, batch, labelling.dist, labelling.hub, hub_mask, plan,
            streams)
    else:
        aff, search_waves = search_basic_planes(g_new, batch, labelling.dist,
                                                plan, streams)
    new_key2, repair_waves = repair_planes(g_new, aff, labelling.key2(),
                                           hub_mask, plan, streams)
    counts = {"update_waves": {"search": search_waves,
                               "repair": repair_waves},
              "stream_builds": jnp.asarray(2)}
    return g_new, relabel(labelling, new_key2), aff, counts


def batchhl_update(g_old: Graph, batch: BatchUpdate,
                   labelling: HighwayLabelling, improved: bool = True,
                   plan: RelaxPlan | None = None,
                   g_new: Graph | None = None, *,
                   stats: dict | None = None
                   ) -> tuple[Graph, HighwayLabelling, jax.Array]:
    """One BatchHL step: apply B, search, repair. Returns (G', Γ', aff).

    `plan` selects the sweep backend (engine.RelaxEngine.prepare); it must
    be prepared from the *post-update* snapshot G' = apply_batch(g_old,
    batch) so the tiling covers edges the batch inserts (launch/serve.py
    shows the amortized pattern). plan=None runs the jnp reference.
    Callers that already materialized G' (typically for that prepare) can
    pass it as `g_new` to skip the recompute; it must equal
    apply_batch(g_old, batch). `stats`, when given, receives the
    program's counts (`batchhl_update_counted`), as device scalars.
    """
    g_new, new_labelling, aff, counts = batchhl_update_counted(
        g_old, batch, labelling, improved, plan, g_new)
    if stats is not None:
        stats.update(counts)
    return g_new, new_labelling, aff


def batchhl_update_split(g_old: Graph, batch: BatchUpdate,
                         labelling: HighwayLabelling, improved: bool = True,
                         engine: RelaxEngine | None = None):
    """BHL^s: insertions and deletions as two sequential sub-batches.

    Takes the `RelaxEngine` (not a plan): the tiling must cover the
    intermediate insertion-applied snapshot, and the deletion sub-batch then
    reuses it unchanged (deletions never move topology slots).
    """
    # Re-weights ride the deletion sub-batch: like deletions they touch a
    # live slot and never move topology, so the tiling prepared for the
    # insertion-applied snapshot stays valid through them.
    ins = dataclasses.replace(
        batch, valid=batch.valid & ~batch.is_del & ~batch.is_rew)
    dele = dataclasses.replace(
        batch, valid=batch.valid & (batch.is_del | batch.is_rew))
    plan = None
    g_ins = None
    if engine is not None:
        g_ins = apply_batch(g_old, ins)
        plan = engine.prepare(g_ins)
    g1, lab1, aff1 = batchhl_update(g_old, ins, labelling, improved, plan,
                                    g_new=g_ins)
    if engine is not None:
        # The deletion sub-batch only flips validity bits of the snapshot
        # just tiled — structurally safe, skip the fingerprint sync.
        plan = engine.prepare(g1, topology_changed=False, verify_cache=False)
    g2, lab2, aff2 = batchhl_update(g1, dele, lab1, improved, plan)
    return g2, lab2, aff1 | aff2


def uhl_update(g_old: Graph, batch: BatchUpdate,
               labelling: HighwayLabelling, improved: bool = True,
               engine: RelaxEngine | None = None):
    """UHL+: the single-update baseline — one BatchHL call per update.

    With an engine, re-tiles only on insertion steps (deletions reuse the
    cached tiling) — the per-update amortization the engine contract allows.
    """
    g, lab = g_old, labelling
    total_aff = jnp.zeros_like(labelling.hub)
    u = batch.src.shape[0]
    # One device→host pull for the whole loop: indexing the device arrays
    # inside it (bool(~batch.is_del[i] & ...)) would force a blocking sync
    # per update, serializing the unit-update baseline on transfer latency.
    is_del_h = np.asarray(batch.is_del)
    is_rew_h = np.asarray(batch.is_rew)
    valid_h = np.asarray(batch.valid)
    for i in range(u):
        single = BatchUpdate(batch.src[i:i + 1], batch.dst[i:i + 1],
                             batch.is_del[i:i + 1], batch.valid[i:i + 1],
                             batch.w[i:i + 1], batch.is_rew[i:i + 1])
        plan, g_next = None, None
        if engine is not None:
            # Only insertions move topology slots; deletions and
            # re-weights touch live slots in place.
            is_ins = bool(~is_del_h[i] & ~is_rew_h[i] & valid_h[i])
            g_next = apply_batch(g, single)
            # Deletion steps only flip validity bits of the snapshot the
            # engine last tiled — structurally safe, so skip the
            # fingerprint's per-step host sync (see engine.prepare).
            plan = engine.prepare(g_next, topology_changed=is_ins,
                                  verify_cache=False)
        g, lab, aff = batchhl_update(g, single, lab, improved, plan,
                                     g_new=g_next)
        total_aff = total_aff | aff
    return g, lab, total_aff
