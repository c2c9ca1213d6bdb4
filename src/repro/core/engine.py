"""The relaxation engine: one backend-dispatch seam for every sweep.

Every edge-relaxation wave in the system — offline construction
(`core/construct.py`), batch search Algos 2–3 and batch repair Algo 4
(`core/batch.py`), and the bounded-BiBFS frontier expansion
(`core/query.py`) — is an instance of one primitive:

    cand[v] = min over valid edges (u, v) of extend(keys[u], v)
    extend(k, v) = min(k + step, inf), with `clear_bit` cleared when v is
                   a hub landmark (the ⊕ operator on key2/key4 encodings,
                   see DESIGN.md §1–§2)

`relax_sweep` below routes that primitive through either the pure-jnp
segment-min reference (XLA scatter-min) or the tiled Pallas `edge_relax`
kernel, selected by the `RelaxPlan`'s static backend tag — the same
dispatch shape as `query_upper_bound(use_kernel=...)` → the minplus kernel.

The Pallas path needs a destination-block tiling of the edge list
(`BlockedGraph`).  Tiling is a host-side O(E log E) sort, so `RelaxEngine`
caches it per graph snapshot and rebuilds only when topology slots change:
deletions merely flip validity bits (re-tiled on device through the
stored slot permutation, once per fixpoint phase: `sweep_streams`), while
insertions rewrite src/dst slots and invalidate the tiling (see DESIGN.md
§3 for the full contract).
`launch/serve.py` holds one engine for the serving loop so the tiling is
amortized across all waves of a tick and across deletion-only ticks.

Plans are mesh-transparent: the tiling is organized as `shards` contiguous
block_v-aligned vertex shards (the leading tile axis, bit-identical for
every shard count), and `core/shard.py` passes the whole plan into its
`shard_map` bodies as replicated leaves — every device launches the same
kernel over its local landmark planes. One prepared plan therefore serves
sharded and unsharded call-sites alike; a mesh→no-mesh round trip keeps
the cache (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.coo import Graph
from repro.graphs.segment import masked_segment_min
from repro.core import autotune as tune_mod
from repro.kernels.edge_relax import ops as er_ops
from repro.kernels.edge_relax import ref as er_ref
from repro.kernels.edge_relax.ops import (BlockedGraph, FrontierTiles,
                                          SortedGraph, SweepStreams)

BACKENDS = ("jnp", "pallas")


@partial(jax.tree_util.register_dataclass,
         data_fields=("tiles", "sorted_tiles", "frontier"),
         meta_fields=("backend", "impl"))
@dataclasses.dataclass(frozen=True)
class RelaxPlan:
    """How to run sweeps on one graph snapshot.

    A pytree: `tiles` / `sorted_tiles` / `frontier` (the prepared edge
    representations, None when unused) flow through jit as data;
    `backend` and `impl` are metadata, so dispatch below is resolved at
    trace time — each (backend, impl) gets its own executable, with no
    runtime branching inside the compiled sweep loops.

    `impl` selects the Pallas-backend implementation the autotuner picked
    (see `core/autotune.py`): "kernel" = the tiled Pallas kernel on
    `tiles`, "sorted" = the dst-sorted compiled segment-min twin on
    `sorted_tiles`. Both are bit-identical to the jnp reference.

    `frontier` (any backend) carries the change-propagation row tiling
    that lets `core/batch.py` relax only the destination blocks the
    batch's frontier touches (DESIGN.md §10). Whether it is present is
    pytree *structure*, so the fixpoint loops specialize at trace time:
    plans without it compile exactly the pre-frontier full-sweep program.
    """
    tiles: BlockedGraph | None
    backend: str
    sorted_tiles: SortedGraph | None = None
    impl: str = "kernel"
    frontier: FrontierTiles | None = None


#: Default plan: the pure-jnp reference path, no tiling required.
JNP_PLAN = RelaxPlan(tiles=None, backend="jnp")


def sweep_streams(plan: RelaxPlan | None, g: Graph,
                  edge_mask: jax.Array | None = None,
                  hub: jax.Array | None = None) -> SweepStreams:
    """The per-edge operands of `relax_sweep` that its keys do not touch,
    in the edge order of the plan's implementation: the edge mask
    (default g.valid; [E], or [P, E] per landmark plane), g's weights,
    and the hub flag of each edge's destination (from `hub` [P, V], or
    None). A fixpoint phase builds them once and passes them to every
    wave, vmapped over planes with `SweepStreams.plane_axes`.
    """
    mask = g.valid if edge_mask is None else edge_mask
    if plan is None or plan.backend == "jnp":
        return SweepStreams(mask, g.w,
                            None if hub is None else hub[..., g.dst],
                            mask.ndim == 2)
    if plan.backend == "pallas":
        if plan.impl == "sorted":
            return plan.sorted_tiles.streams(mask, g.w, hub)
        return plan.tiles.streams(mask, g.w, hub)
    raise ValueError(f"unknown backend {plan.backend!r}; pick from {BACKENDS}")


def relax_sweep(plan: RelaxPlan | None, g: Graph, keys: jax.Array,
                step, inf, *, hub: jax.Array | None = None,
                clear_bit: int = 0,
                edge_mask: jax.Array | None = None,
                streams: SweepStreams | None = None) -> jax.Array:
    """One relaxation wave of `keys` [V] over the edges of `g`.

    plan=None (or backend "jnp") runs the segment-min reference on the COO
    arrays; backend "pallas" runs the tiled kernel (interpret-mode off-TPU,
    so results are bit-identical across backends — the parity tests assert
    this). `edge_mask` defaults to g.valid and is always in original
    edge-slot order; `hub`/`clear_bit` realize key2/key4 path extension.

    `streams` (one plane's slice of `sweep_streams`) carries the mask,
    weights and hub flags built once for the phase; an `edge_mask` given
    with it replaces its mask for this one sweep. Without `streams` all
    three are built here.

    The metric is weighted: the extend adds step·w(u,v) from the graph's
    per-slot weight column and saturates at `inf` (int32 wrap → inf).
    Unweighted graphs carry w ≡ 1 on occupied slots, which makes the
    weighted extend bit-identical to the historical `keys + step`.
    """
    if streams is None:
        streams = sweep_streams(plan, g, edge_mask, hub)
    elif edge_mask is not None:
        streams = dataclasses.replace(
            streams, mask=sweep_streams(plan, g, edge_mask).mask)
    if plan is None or plan.backend == "jnp":
        s = keys[g.src] + step * streams.w
        cand = jnp.minimum(jnp.where(s < 0, inf, s), inf)
        if streams.hub is not None and clear_bit:
            cand = jnp.where(streams.hub, cand & ~jnp.int32(clear_bit), cand)
        return masked_segment_min(cand, g.dst, g.n, streams.mask, inf)
    if plan.impl == "sorted":
        return er_ops.relax_sweep_sorted(keys, plan.sorted_tiles, None, step,
                                         inf, clear_bit=clear_bit,
                                         streams=streams)
    return er_ops.relax_sweep(keys, plan.tiles, None, step, inf,
                              clear_bit=clear_bit, streams=streams)


def frontier_or_sweep(plan: RelaxPlan | None, g: Graph, lanes: int):
    """The bit-packed BFS's sweep on `g`'s valid edges, as a function
    `sweep(words)`: packed frontier words [W, V] uint32 → the OR of the
    words of every in-neighbour [W, V] (bit q of a word is one query's
    lane, of `lanes` in all). Unweighted: it is a BFS level step, not a
    relaxation.

    What does not change across waves — the validity mask re-tiled to
    the plan's edge order — is computed here, once per search, outside
    the wave loop. Dispatch follows `relax_sweep`, but plan=None, "jnp"
    and a "sorted" plan alike reduce the COO arrays bit by bit, over the
    min(lanes, 32) bits in use (an OR does not depend on edge order);
    the "kernel" impl runs the Pallas `frontier_or` kernel.
    """
    if plan is None or plan.backend == "jnp" or plan.impl == "sorted":
        nbits = min(lanes, 32)
        return lambda words: er_ref.frontier_or(words, g.src, g.dst,
                                                g.valid, g.n, nbits)
    if plan.backend == "pallas":
        dst_t = plan.tiles.masked_dst(g.valid)
        return lambda words: er_ops.frontier_or(words, plan.tiles, dst_t)
    raise ValueError(f"unknown backend {plan.backend!r}; pick from {BACKENDS}")


def gather_rows(plan: RelaxPlan, g: Graph, ridx: jax.Array):
    """Materialize the masked sweep's active tile rows (plane-independent).

    `ridx` int32[rows_cap] names tile rows of `plan.frontier`, sentinel-
    filled to its static size. Returns (src_g, dstg, valid_g, w_g), each
    [rows_cap, BE]: source vertex, global destination vertex, per-slot
    validity (tile occupancy ∧ current edge validity through the stored
    slot permutation — the same device re-tiling trick BlockedGraph
    uses), and edge weight. Gathered once per wave, shared by every
    landmark plane's `relax_rows`.
    """
    src_g, dstg, perm_g, slot_g = plan.frontier.gather(ridx)
    valid_g = slot_g & g.valid[perm_g]
    w_g = jnp.where(slot_g, g.w[perm_g], 0)
    return src_g, dstg, valid_g, w_g


def relax_rows(keys: jax.Array, out: jax.Array, src_g, dstg, emask_g, w_g,
               step, inf, *, hub: jax.Array | None = None,
               clear_bit: int = 0, bound: jax.Array | None = None
               ) -> jax.Array:
    """One masked relaxation wave: scatter-min row candidates into `out`.

    The same extend/hub-clear math as `relax_sweep`, restricted to the
    gathered rows: candidates from masked-off slots (and the sentinel
    fill rows, whose dstg is 0 and emask false) become `inf`, so the
    scatter-min is a no-op for them. `bound`, when given, applies the
    per-destination acceptance filter (`cand <= bound[dst]`) per edge —
    equivalent because the bound is constant per destination, and
    required here because the masked path never materializes the
    per-destination segment min before combining into `out`.
    """
    s = keys[src_g] + step * w_g
    cand = jnp.minimum(jnp.where(s < 0, inf, s), inf)
    if hub is not None and clear_bit:
        cand = jnp.where(hub[dstg], cand & ~jnp.int32(clear_bit), cand)
    if bound is not None:
        cand = jnp.where(cand <= bound[dstg], cand, inf)
    cand = jnp.where(emask_g, cand, inf)
    return out.at[dstg.ravel()].min(cand.ravel())


class RelaxEngine:
    """Host-side owner of the backend choice and the tiling cache.

    backend:  "jnp"    — segment-min reference everywhere (the default off
                         TPU; zero host syncs, zero tiling cost),
              "pallas" — tiled kernel (compiled on TPU, interpret-mode
                         elsewhere; parity-tested against jnp),
              "auto"   — "pallas" on TPU, "jnp" otherwise.
    block_v:  destination-block size for the tiling (kernel output tile).
    shards:   vertex-shard count of the tiling (leading tile axis; the
              kernel grid walks (shard, block)). Bit-identical for every
              value — a launch-structure knob that lets the plan compose
              with `shard_map` meshes (`core/shard.py`) and, at scale,
              lets each device own one slice.
    """

    def __init__(self, backend: str = "auto", block_v: int = 512,
                 shards: int = 1, cache_plans: int = 2,
                 block_e: int | None = None, autotune: bool = False,
                 tune_table: "tune_mod.TuneTable | str | None" = None,
                 frontier: bool = False, frontier_threshold: float = 0.25,
                 frontier_block: int = 64):
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; pick from {BACKENDS + ('auto',)}")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if cache_plans < 1:
            raise ValueError(f"cache_plans must be >= 1, got {cache_plans}")
        self.backend = backend
        self.block_v = block_v
        self.shards = shards
        self.block_e = block_e
        self.cache_plans = cache_plans
        # Frontier-proportional sweeps (DESIGN.md §10): when enabled,
        # prepared plans additionally carry the change-propagation row
        # tiling so batch search/repair can relax only the destination
        # blocks the batch footprint touches. Orthogonal to the backend —
        # even jnp plans get tiled (and therefore pay the tiling sync).
        self.frontier = frontier
        self.frontier_threshold = frontier_threshold
        self.frontier_block = frontier_block
        # Autotuning (core/autotune.py): pick impl + tile shape per
        # snapshot shape, memoized in a TuneTable (optionally on disk so
        # serve restarts skip the measurement entirely).
        self.autotune = autotune
        if isinstance(tune_table, str):
            tune_table = tune_mod.TuneTable(tune_table)
        self.tune_table = (tune_table if tune_table is not None
                           else (tune_mod.TuneTable() if autotune else None))
        self._tuned_cfg: tune_mod.TuneConfig | None = None
        self._plan: RelaxPlan | None = None
        self._fingerprint: tuple | None = None
        # Fingerprint-keyed LRU of prepared plans. The serving pipeline
        # keeps two snapshots live at once (committed N answering queries,
        # N+1 under construction), so re-preparing for either must not
        # thrash an O(E log E) retile — the default capacity of 2 covers
        # exactly that pattern. The key also carries the tuned config, so
        # adopting a new winner can never serve tiles shaped for the old
        # one. Prepared plans are immutable, so evicted entries embedded
        # in older snapshots stay valid.
        self._plans: dict[tuple, RelaxPlan] = {}
        self.retile_count = 0  # observability: serve/benchmarks report this
        self.stale_cache_retiles = 0  # fingerprint mismatches caught below
        self.plan_cache_hits = 0  # keyed-cache hits (no retile needed)
        self.tune_count = 0  # tuner measurement runs (table misses)

    @property
    def plan_alignment(self) -> int:
        """Vertex-count alignment unit for grow-in-place (DESIGN.md §6).

        Grown vertex counts are rounded up to block_v · shards so the
        grown tiling keeps full destination blocks and an even per-shard
        block split — the same shape a fresh prepare at that size would
        produce. Reported for *both* backends (the jnp path needs no
        alignment) so a growth stream reaches the same sizes whichever
        backend serves it, keeping cross-backend state bit-comparable.
        """
        return self.block_v * self.shards

    @staticmethod
    def _snapshot_fingerprint(g: Graph) -> tuple:
        """Cheap identity of a snapshot's topology slots.

        (n, capacity, occupied-slot count, all-slot src/dst checksum). The
        checksum covers *every* slot — free slots included — because
        insertions rewrite free slots (changing it) while deletions only
        flip validity bits (leaving it untouched). It is *slot-position
        sensitive* — each slot's hash is mixed with its index — because
        the tiling a fingerprint keys embeds a slot permutation: two
        snapshots holding the same edge multiset in different slot
        layouts must not collide, or one's per-slot validity mask gets
        applied through the other's permutation and the sweep relaxes
        the wrong edges (a commutative sum had exactly this collision;
        the batch-split property test pins it). n and capacity being
        part of the key is what makes grow-in-place safe here: a grown
        snapshot can never alias a pre-growth fingerprint, so growth is
        always a clean retile, never a stale-tile reuse (DESIGN.md §6).
        Two tiny device reductions + one host sync; negligible next to
        the O(E log E) retile it guards.
        """
        occupied = int(jnp.sum(g.valid))
        idx = jnp.arange(g.src.shape[0], dtype=jnp.uint32)
        slot_h = (g.src.astype(jnp.uint32) * jnp.uint32(2654435761)
                  + g.dst.astype(jnp.uint32) * jnp.uint32(40503)) \
            ^ (idx * jnp.uint32(2246822519))
        chk = int(jnp.sum(slot_h))
        return (g.n, g.src.shape[0], occupied, chk)

    def _cache_is_stale(self, g: Graph) -> bool:
        """True when `g`'s topology slots don't match the cached tiling.

        Legitimate reuse (deletion-only churn since tiling) keeps n,
        capacity, and the all-slot checksum fixed and can only *shrink* the
        occupied count; anything else — an insertion the caller forgot to
        flag, or a different graph entirely — mismatches.
        """
        n, cap, occupied, chk = self._fingerprint
        n2, cap2, occupied2, chk2 = self._snapshot_fingerprint(g)
        return (n2, cap2, chk2) != (n, cap, chk) or occupied2 > occupied

    def prepare(self, g: Graph, topology_changed: bool = True,
                verify_cache: bool = True) -> RelaxPlan:
        """Plan sweeps for snapshot `g`, reusing the cached tiling when the
        caller can vouch that no topology slot changed since the last
        prepare (deletion-only batches flip validity bits only).

        The vouch is verified: a snapshot fingerprint recorded at tiling
        time is re-checked on every cache hit, and a mismatch (slots
        changed, or a different graph entirely) forces a retile instead of
        silently serving stale tiles (counted in `stale_cache_retiles`).
        The check costs two small device reductions + a host sync;
        `verify_cache=False` skips it for tight inner loops whose snapshot
        is *derived* from the tiled one by deletions alone (the engine's
        own variant drivers, `uhl_update`/`batchhl_update_split`, where a
        per-step sync would serialize the loop on transfer latency).

        Topology changes route through a fingerprint-keyed LRU (capacity
        `cache_plans`): preparing a snapshot whose slots match a cached
        tiling — e.g. alternating between the two live snapshots of the
        serving pipeline — returns it without the O(E log E) retile
        (`plan_cache_hits` counts these; the fingerprint sync is the same
        one a retile would pay).

        On the jnp backend this is free — no tiling, no host sync —
        unless `frontier` is enabled, in which case jnp plans carry (and
        cache) the change-propagation tiling like any other and pay the
        same fingerprint sync.
        """
        if self.backend == "jnp" and not self.frontier:
            return JNP_PLAN
        cfg = self._ensure_tuned(g) if self.backend == "pallas" else None
        if self._plan is not None and not topology_changed:
            if not (verify_cache and self._cache_is_stale(g)):
                return self._plan
            self.stale_cache_retiles += 1  # the vouch was wrong — re-key
        fp = self._snapshot_fingerprint(g)
        key = fp + ((cfg.impl, cfg.block_v, cfg.block_e, cfg.tile_shards)
                    if cfg else ())
        if self.frontier:
            key = key + ("frontier", self.frontier_block,
                         self.frontier_threshold)
        plan = self._plans.pop(key, None)
        if plan is None:
            # Host sync: pull the slot arrays once per topology change and
            # prepare only the occupied slots (free slots get src/dst
            # rewritten by the insertion that occupies them, forcing a
            # re-prepare).
            src = np.asarray(g.src)
            dst = np.asarray(g.dst)
            keep = np.asarray(g.valid)
            ft = (er_ops.prepare_frontier(
                      src, dst, keep, g.n, self.frontier_block,
                      threshold=self.frontier_threshold)
                  if self.frontier else None)
            if self.backend == "jnp":
                plan = RelaxPlan(tiles=None, backend="jnp", frontier=ft)
            elif cfg is not None and cfg.impl == "sorted":
                plan = RelaxPlan(tiles=None, backend="pallas",
                                 sorted_tiles=er_ops.prepare_sorted(
                                     src, dst, keep, g.n),
                                 impl="sorted", frontier=ft)
            else:
                tiling_s = cfg.tile_shards if cfg else self.shards
                plan = RelaxPlan(tiles=er_ops.prepare_topology(
                    src, dst, keep, g.n, self.block_v, tiling_s,
                    self.block_e), backend="pallas", frontier=ft)
            self.retile_count += 1
        else:
            self.plan_cache_hits += 1
        self._plans[key] = plan  # (re)insert as most-recently used
        while len(self._plans) > self.cache_plans:
            self._plans.pop(next(iter(self._plans)))
        self._plan, self._fingerprint = plan, fp
        return plan

    def _ensure_tuned(self, g: Graph) -> "tune_mod.TuneConfig | None":
        """Resolve (and adopt) the tuned config for `g`'s shape.

        Table lookups are keyed (n, capacity, shards) — edge churn at
        fixed shape reuses the winner with zero measurement; growth
        changes the key and re-tunes (`tune_count` counts measurement
        runs). Adopting a kernel-impl winner updates `block_v`/`block_e`
        so `plan_alignment` — the contract `core/growth.py` sizes grown
        snapshots against — always reflects the tiles actually served.
        """
        if not self.autotune:
            return None
        key = tune_mod.table_key(g.n, int(g.src.shape[0]), self.shards)
        cfg = self.tune_table.get(key)
        if cfg is None:
            result = tune_mod.tune(g, shards=self.shards,
                                   block_v=self.block_v)
            self.tune_table.put(key, result)
            self.tune_count += 1
            cfg = result.config
        if cfg != self._tuned_cfg:
            self._tuned_cfg = cfg
            if cfg.impl == "kernel":
                self.block_v = cfg.block_v
                self.block_e = cfg.block_e
            if cfg.frontier_threshold is not None:
                self.frontier_threshold = cfg.frontier_threshold
        return cfg
