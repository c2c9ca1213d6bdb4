"""Jit'd wrappers: tiled Pallas edge relaxation with jnp fallback.

`BlockedGraph` carries the one-off destination-block tiling, organized as
`shards` contiguous block_v-aligned vertex shards (leading [S] axis on every
tile array; S=1 is the classic unsharded tiling). Tile rows are
[S, NR, BE]: without a `block_e` cap one row per destination block
(NR = NB), with one a tuned cap that chunks oversized blocks into several
consecutive rows (`rowblk_t` names each row's block — see
`kernel.block_edges_topology`). The tiling is purely topological
(src / local-dst / original-slot permutation): edge validity — which
churns with every batch update and with the repair boundary/interior
masks — is re-tiled on device with a single gather through `perm_t`, so
re-tiling on host is needed only when topology slots change (insertions
rewrite src/dst), not per deletion. The per-edge operands a sweep reads
besides the source keys (mask, weights, destination hub flags) form a
`SweepStreams`; a fixpoint builds them once per phase and hands them to
every wave, so a wave gathers only its source keys.
Because no destination block straddles a shard boundary, sweep results are
bit-identical for every S — the shard axis only shapes the launch grid
(and, under a mesh, which slice a device owns). `core/engine.py` owns the
cache; this module owns the kernel launch.

`SortedGraph` is the second prepared representation the autotuner can
pick (`impl="sorted"`): the kept edge slots fully sorted by destination.
Its sweep is the same math lowered through XLA's sorted segment-min — a
compiled executable on every platform, where the Pallas kernel runs
interpret-mode off-TPU. Besides the sorted-reduction lowering it sweeps
only the *occupied* slots (the jnp reference sweeps every capacity slot),
which is where the measured win over the reference comes from on
slack-provisioned serving snapshots.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.edge_relax import kernel, ref


@partial(jax.tree_util.register_dataclass,
         data_fields=("mask", "w", "hub"), meta_fields=("plane_mask",))
@dataclasses.dataclass(frozen=True)
class SweepStreams:
    """The per-edge operands of a sweep that do not depend on its keys,
    in the edge order of the sweep's implementation (`[S, NR, W]` tile
    slots, dst-sorted slots, or the COO slots).

    They stay fixed across the waves of one fixpoint phase, so the phase
    builds them once and every wave reads them; the wave itself gathers
    only the source keys. Bit-identical to building them inside the wave:
    the same gathers on the same operands.
    """
    mask: jax.Array          # edge mask (int32 tiles, else bool);
                             # [P, ...] when `plane_mask`
    w: jax.Array | None      # edge weights (None: the unit metric)
    hub: jax.Array | None    # hub flag of each edge's destination, [P, ...]
                             # (None: no hub bit-clear)
    plane_mask: bool = False  # the mask differs per landmark plane

    def plane_axes(self) -> "SweepStreams":
        """`jax.vmap` in_axes over the landmark planes: the hub flags
        and a per-plane mask are split, the weights are shared."""
        return SweepStreams(0 if self.plane_mask else None, None,
                            None if self.hub is None else 0,
                            self.plane_mask)


def _planewise(fn, x: jax.Array, ndim: int) -> jax.Array:
    """`fn` on `x`, or on each plane of `x` if it has a leading plane
    axis over the `ndim` dimensions `fn` takes."""
    return fn(x) if x.ndim == ndim else jax.vmap(fn)(x)


@partial(jax.tree_util.register_dataclass,
         data_fields=("src_t", "dstloc_t", "valid_t", "perm_t", "slot_t",
                      "rowblk_t"),
         meta_fields=("n", "block_v", "nb", "chunked"))
@dataclasses.dataclass(frozen=True)
class BlockedGraph:
    src_t: jax.Array     # int32[S, NR, BE] source vertex per tile slot
    dstloc_t: jax.Array  # int32[S, NR, BE] destination local to the block
    valid_t: jax.Array   # int32[S, NR, BE] validity baked at prepare time
    perm_t: jax.Array    # int32[S, NR, BE] original edge-slot index
    slot_t: jax.Array    # int32[S, NR, BE] 1 on real slots, 0 on padding
    rowblk_t: jax.Array  # int32[S, NR] local destination block of each row
    n: int
    block_v: int
    nb: int              # destination blocks per shard (NR >= nb)
    chunked: bool        # tile rows are not one per destination block
    # `chunked` is recorded at prepare time from the pre-shard row count
    # and the padded NR_loc: post-shard shapes cannot distinguish a
    # chunked tiling whose extra rows fit inside a short last shard
    # (NR_loc == nb_loc) from an unchunked one, and skipping the row fold
    # there drops relaxations.

    @property
    def shards(self) -> int:
        """Vertex-shard count S of the tiling (leading tile axis)."""
        return self.src_t.shape[0]

    def tile_mask(self, edge_mask: jax.Array) -> jax.Array:
        """Re-tile a per-edge mask (original slot order) on device."""
        if edge_mask.shape[0] == 0:  # zero-capacity graph: all-pad tiles
            return jnp.zeros_like(self.slot_t)
        return jnp.where(self.slot_t != 0,
                         edge_mask[self.perm_t], False).astype(jnp.int32)

    def tile_w(self, w: jax.Array | None) -> jax.Array:
        """Re-tile per-edge weights (original slot order) on device.

        `w=None` means the unweighted metric: slot_t doubles as the unit
        weight tile (1 on real slots, 0 on padding — padding is masked to
        inf anyway). Weights churn with re-weight batches the way validity
        churns with deletions, so they ride the same stored permutation and
        never force a host-side re-tile.
        """
        if w is None or w.shape[0] == 0:
            return self.slot_t
        return jnp.where(self.slot_t != 0, w[self.perm_t], 0).astype(jnp.int32)

    def masked_dst(self, edge_mask: jax.Array) -> jax.Array:
        """Local destinations [S, NR, BE] with -1 on the slots that
        `edge_mask` (original slot order) or padding leaves out: the
        OR sweep's one per-edge stream besides the source words."""
        return jnp.where(self.tile_mask(edge_mask) != 0, self.dstloc_t, -1)

    def tile_plane(self, plane: jax.Array, fill) -> jax.Array:
        """Pad + reshape a per-vertex plane [V] to dst tiles [S, NB, BV]."""
        s = self.src_t.shape[0]
        npad = s * self.nb * self.block_v
        padded = jnp.full((npad,), fill, plane.dtype).at[:self.n].set(plane)
        return padded.reshape(s, self.nb, self.block_v)

    def tile_plane_rows(self, plane: jax.Array, fill) -> jax.Array:
        """Per-vertex plane [V] → per-*row* dst tiles [S, NR, BV].

        The chunked kernel grid walks tile rows, so per-destination data
        (hub flags) is gathered out to one tile per row; rows of the same
        block share the block's tile. Collapses to `tile_plane` when the
        tiling is unchunked.
        """
        blocks = self.tile_plane(plane, fill)
        if not self.chunked:
            return blocks
        return jnp.take_along_axis(blocks, self.rowblk_t[..., None], axis=1)

    def tile_hub(self, hub: jax.Array) -> jax.Array:
        """Per-vertex hub flags [V] → the flag of each tile slot's
        destination, int32 [S, NR, BE]."""
        return jnp.take_along_axis(
            self.tile_plane_rows(hub.astype(jnp.int32), 0), self.dstloc_t,
            axis=2)

    def streams(self, edge_mask: jax.Array, w: jax.Array | None,
                hub: jax.Array | None) -> SweepStreams:
        """The sweep's streams on this tiling. `edge_mask` is [E] or
        per plane [P, E], `hub` per plane [P, V] (or [V] for one plane),
        both in original slot / vertex order."""
        return SweepStreams(
            _planewise(self.tile_mask, edge_mask, 1), self.tile_w(w),
            None if hub is None else _planewise(self.tile_hub, hub, 1),
            edge_mask.ndim == 2)


@partial(jax.tree_util.register_dataclass,
         data_fields=("src_r", "dstg_r", "perm_r", "slot_r", "rowblk_r",
                      "adj"),
         meta_fields=("n", "fblock", "nbf", "nrows", "rows_cap"))
@dataclasses.dataclass(frozen=True)
class FrontierTiles:
    """The third prepared representation: change-propagation row tiling.

    Groups the kept edge slots into destination-block rows (the same
    host-side tiling `BlockedGraph` uses, at its own — typically finer —
    block size `fblock`), plus the block-adjacency matrix that propagates
    an active frontier one tile-neighbourhood per wave. A masked sweep
    gathers only the rows of active destination blocks through a
    static-size index vector (`jnp.nonzero(size=rows_cap,
    fill_value=nrows)` — the ragged-segment/padding shape discipline, so
    shapes stay static under jit) and scatter-mins their candidates into
    the key plane; row `nrows` is an all-padding sentinel that absorbs
    the fill slots as no-ops. Backend-independent: all three sweep impls
    (jnp, sorted, kernel) share this masked path and fall back to their
    own full sweep — bit-identically — when the frontier densifies past
    `rows_cap` (see DESIGN.md §10).
    """
    src_r: jax.Array     # int32[NR+1, BE] source vertex (row NR: sentinel)
    dstg_r: jax.Array    # int32[NR+1, BE] global destination vertex
    perm_r: jax.Array    # int32[NR+1, BE] original edge-slot index
    slot_r: jax.Array    # int32[NR+1, BE] 1 on real slots, 0 on padding
    rowblk_r: jax.Array  # int32[NR] destination block per row (nbf on
                         # bucket-padding rows: the never-active sentinel)
    adj: jax.Array       # bool[NBf, NBf] block u holds an edge into block v
    n: int
    fblock: int          # frontier block size (vertices per block)
    nbf: int             # number of frontier blocks = ceil(n / fblock)
    nrows: int           # tile rows NR, bucketed to a multiple of 64 so
                         # shapes stay trace-stable across edge churn
                         # (sentinel gather row lives at index NR)
    rows_cap: int        # masked-sweep row budget (density threshold)

    def propagate(self, front: jax.Array) -> jax.Array:
        """Blocks reachable in one wave from changed blocks `front` [NBf].

        active[bv] = ∃ bu: front[bu] ∧ adj[bu, bv] — every destination
        block that receives an edge from a changed block must relax this
        wave; all others provably cannot improve (DESIGN.md §10).
        """
        return jnp.any(self.adj & front[:, None], axis=0)

    def changed_blocks(self, changed_v: jax.Array) -> jax.Array:
        """Per-vertex changed flags [..., V] → per-block flags [..., NBf]."""
        pad = self.nbf * self.fblock - self.n
        lead = changed_v.shape[:-1]
        padded = jnp.concatenate(
            [changed_v, jnp.zeros(lead + (pad,), changed_v.dtype)], axis=-1)
        return jnp.any(padded.reshape(lead + (self.nbf, self.fblock)),
                       axis=-1)

    def active_rows(self, active_blocks: jax.Array) -> jax.Array:
        """Active-block flags [NBf] → tile-row flags [NR].

        Bucket-padding rows carry `rowblk = nbf`, which indexes the
        appended always-False slot — they never activate.
        """
        never = jnp.zeros((1,), dtype=active_blocks.dtype)
        return jnp.concatenate([active_blocks, never])[self.rowblk_r]

    def gather(self, ridx: jax.Array):
        """Materialize the rows named by `ridx` (static size, sentinel-
        filled): (src [K, BE], dst-global [K, BE], perm [K, BE],
        slot [K, BE] bool)."""
        return (self.src_r[ridx], self.dstg_r[ridx], self.perm_r[ridx],
                self.slot_r[ridx] != 0)


def prepare_frontier(src, dst, keep, n: int, fblock: int = 64,
                     block_e: int | None = 128,
                     threshold: float = 0.25) -> FrontierTiles:
    """Build the change-propagation tiling (host sync, once per topology).

    `fblock` is the frontier granularity: smaller blocks track a tight
    batch footprint more precisely but grow the adjacency matrix
    (NBf² bits) and the row count. `block_e` caps row width the way the
    kernel tiling's block_e does (oversized blocks chunk into several
    rows), keeping the masked gather's [rows_cap, BE] working set small
    on power-law hub blocks. `threshold` is the density-fallback knob:
    the masked sweep runs while the active rows fit within
    ceil(threshold · NR); denser frontiers fall back to the full sweep
    (autotunable — `core/autotune.py:tune_frontier_threshold`).
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    keep = np.asarray(keep, bool)
    src_t, dstloc_t, perm_t, slot_t, rowblk, fb = kernel.block_edges_topology(
        src, dst, keep, n, fblock, block_e)
    nr, be = src_t.shape
    nbf = -(-n // fb)
    dstg_t = np.where(slot_t != 0, rowblk[:, None] * fb + dstloc_t, 0)
    # Bucket the row count to a multiple of 64: the row arrays' shapes
    # (and rows_cap below) are jit-trace constants, so letting NR drift
    # with every inserted edge would retrace the whole update per tick —
    # a >1s spike on the serving path. Bucket-padding rows are all
    # padding slots with rowblk = nbf (the always-inactive sentinel
    # block in `active_rows`). The sentinel gather row still lives at
    # index NR (= the bucketed count).
    nr_b = max(64, -(-nr // 64) * 64)
    pad_rows = np.zeros((nr_b - nr + 1, be), np.int32)
    rowblk_b = np.concatenate(
        [rowblk, np.full(nr_b - nr, nbf, np.int32)])
    adj = np.zeros((nbf, nbf), bool)
    if keep.any():
        adj[src[keep] // fb, dst[keep] // fb] = True
    rows_cap = max(1, min(nr_b, int(np.ceil(nr_b * threshold))))
    return FrontierTiles(
        jnp.asarray(np.concatenate([src_t, pad_rows])),
        jnp.asarray(np.concatenate([dstg_t, pad_rows])),
        jnp.asarray(np.concatenate([perm_t, pad_rows])),
        jnp.asarray(np.concatenate([slot_t, pad_rows])),
        jnp.asarray(rowblk_b), jnp.asarray(adj),
        n, fb, nbf, nr_b, rows_cap)


@partial(jax.tree_util.register_dataclass,
         data_fields=("src_s", "dst_s", "perm_s"),
         meta_fields=("n",))
@dataclasses.dataclass(frozen=True)
class SortedGraph:
    """Kept edge slots fully sorted by destination (the `sorted` impl).

    `perm_s` maps each sorted position back to its original edge slot, so
    per-sweep masks re-tile with one gather — the same contract as
    `BlockedGraph.tile_mask`. Sorting is total (by dst vertex, not dst
    block), which is what lets the sweep lower through
    `segment_min(indices_are_sorted=True)`.
    """
    src_s: jax.Array   # int32[M] source vertex, dst-sorted order
    dst_s: jax.Array   # int32[M] destination vertex, ascending
    perm_s: jax.Array  # int32[M] original edge-slot index
    n: int

    def streams(self, edge_mask: jax.Array, w: jax.Array | None,
                hub: jax.Array | None) -> SweepStreams:
        """The sweep's streams in dst-sorted order (see
        `BlockedGraph.streams` for the shapes)."""
        return SweepStreams(
            edge_mask[..., self.perm_s],
            None if w is None else jnp.take(w, self.perm_s, axis=0),
            None if hub is None else jnp.take(hub, self.dst_s, axis=-1),
            edge_mask.ndim == 2)


def prepare(src, dst, valid, n: int, block_v: int = 512,
            shards: int = 1, block_e: int | None = None) -> BlockedGraph:
    """Tile every edge slot; bake `valid` into valid_t (legacy entry)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    valid = np.asarray(valid, bool)
    src_t, dstloc_t, perm_t, slot_t, rowblk, bv = kernel.block_edges_topology(
        src, dst, np.ones(len(src), bool), n, block_v, block_e)
    valid_t = (np.where(slot_t != 0, valid[perm_t].astype(np.int32), 0)
               if len(valid) else np.zeros_like(slot_t))
    nb = -(-n // bv)
    rowblk_t, nb_loc, src_t, dstloc_t, valid_t, perm_t, slot_t = \
        kernel.shard_tiling(shards, nb, rowblk, src_t, dstloc_t,
                            valid_t.astype(np.int32), perm_t, slot_t)
    chunked = len(rowblk) != nb or src_t.shape[1] != nb_loc
    return BlockedGraph(jnp.asarray(src_t), jnp.asarray(dstloc_t),
                        jnp.asarray(valid_t), jnp.asarray(perm_t),
                        jnp.asarray(slot_t), jnp.asarray(rowblk_t),
                        n, bv, nb_loc, chunked)


def prepare_topology(src, dst, keep, n: int, block_v: int = 512,
                     shards: int = 1,
                     block_e: int | None = None) -> BlockedGraph:
    """Tile only the `keep` slots (host sync; amortized by core/engine.py).

    `keep` should be the currently-occupied slots: future deletions only
    flip validity (handled per sweep via `tile_mask`), while insertions
    rewrite src/dst and therefore force a fresh prepare anyway.

    `shards` splits the destination-block tiling into that many contiguous
    vertex shards (the leading [S] tile axis — see `kernel.shard_tiling`);
    `block_e` caps the tile-row width, chunking oversized destination
    blocks into several rows. Results are bit-identical for every S and
    every block_e — both are launch-structure knobs the autotuner sweeps.

    The returned tiling sets `valid_t` to slot *occupancy*, not edge
    validity — it must only be consumed through `relax_sweep`, which
    re-tiles the caller's current per-edge mask via `perm_t` every wave.
    Feeding it to the legacy `edge_relax` (which trusts `valid_t`) would
    treat edges deleted after prepare time as still present.
    """
    src_t, dstloc_t, perm_t, slot_t, rowblk, bv = kernel.block_edges_topology(
        np.asarray(src), np.asarray(dst), np.asarray(keep, bool), n, block_v,
        block_e)
    nb = -(-n // bv)
    rowblk_t, nb_loc, src_t, dstloc_t, perm_t, slot_t = kernel.shard_tiling(
        shards, nb, rowblk, src_t, dstloc_t, perm_t, slot_t)
    chunked = len(rowblk) != nb or src_t.shape[1] != nb_loc
    return BlockedGraph(jnp.asarray(src_t), jnp.asarray(dstloc_t),
                        jnp.asarray(slot_t), jnp.asarray(perm_t),
                        jnp.asarray(slot_t), jnp.asarray(rowblk_t),
                        n, bv, nb_loc, chunked)


def prepare_sorted(src, dst, keep, n: int) -> SortedGraph:
    """Sort the kept edge slots by destination (host sync, once per
    topology — the `sorted` twin of `prepare_topology`)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    keep = np.asarray(keep, bool)
    idx = np.flatnonzero(keep)
    order = np.argsort(dst[idx], kind="stable")
    perm = idx[order].astype(np.int32)
    return SortedGraph(jnp.asarray(src[perm]), jnp.asarray(dst[perm]),
                       jnp.asarray(perm), n)


def edge_relax(keys: jax.Array, bg: BlockedGraph, step,
               use_pallas: bool | None = None) -> jax.Array:
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    interpret = jax.default_backend() != "tpu"
    rowblk_t = bg.rowblk_t if bg.chunked else None
    if use_pallas or interpret is False:
        return kernel.edge_relax_pallas(keys, bg.src_t, bg.dstloc_t,
                                        bg.valid_t, step, bg.n, bg.block_v,
                                        interpret=interpret,
                                        rowblk_t=rowblk_t, nb=bg.nb)
    # jnp fallback on the tiled representation (same math, XLA segment_min).
    s, nr, _ = bg.src_t.shape
    blk = bg.rowblk_t + (jnp.arange(s) * bg.nb)[:, None]      # global block
    flat_dst = bg.dstloc_t + blk[..., None] * bg.block_v
    return ref.edge_relax(keys, bg.src_t.reshape(-1), flat_dst.reshape(-1),
                          bg.valid_t.reshape(-1) != 0, step,
                          s * bg.nb * bg.block_v)[:bg.n]


def relax_sweep(keys: jax.Array, bg: BlockedGraph, edge_mask: jax.Array,
                step, inf, clear_bit=0,
                hub: jax.Array | None = None,
                w: jax.Array | None = None,
                streams: SweepStreams | None = None) -> jax.Array:
    """Generalized relaxation sweep on the tiled graph (Pallas path).

    cand[v] = min over edges (u, v) with edge_mask of
        extend(keys[u]) = clear_bit-cleared-if-hub[v]
                          sat(keys[u] + step·w(u,v), inf)

    `edge_mask` and `w` are in original edge-slot order (length = edge
    capacity); `w=None` is the unweighted metric (w ≡ 1 on real slots).
    `hub` is a per-vertex bool plane [V] (or None for plain relaxation).
    `streams` (`bg.streams`, built once per fixpoint phase) stands for
    all three; without it they are built here, for this one sweep.
    Runs interpret-mode Pallas off-TPU so parity tests exercise the same
    kernel that runs compiled on TPU.
    """
    if streams is None:
        streams = bg.streams(edge_mask, w, hub)
    return relax_sweep_pallas(keys, bg, streams, step, inf, clear_bit,
                              interpret=jax.default_backend() != "tpu")


@partial(jax.jit, static_argnames=("interpret",))
def relax_sweep_pallas(keys: jax.Array, bg: BlockedGraph,
                       streams: SweepStreams, step, inf, clear_bit,
                       interpret: bool = True) -> jax.Array:
    """Launch `kernel._relax_sweep_kernel` over built streams → [V].

    cand[v] = min over masked edges (u, v) of
        clear_hub_bit_if_hub(v, sat(keys[u] + step·w(u,v), inf));
    `inf` if none. Every edge stream arrives built (`streams`: mask,
    weights and, when bits clear, each slot's hub flag) as [C, 128] lane
    rows, and the three scalars in SMEM; the one per-edge gather left
    here is the source keys'. A device trace names the kernel by this
    launch's name. The grid walks (vertex shard, tile row), each step
    owning one disjoint [BV] output tile; with a block_e-chunked tiling
    a sorted segment-min folds the per-row partials (min-of-mins).
    """
    s, nr, w = bg.src_t.shape
    if w % kernel.LANES:
        raise ValueError(f"tile rows must be a multiple of {kernel.LANES} "
                         f"wide, got {w} (see block_edges_topology)")
    params = jnp.stack([jnp.asarray(step, jnp.int32),
                        jnp.asarray(inf, jnp.int32),
                        jnp.asarray(clear_bit, jnp.int32)])
    lanes = (s, nr, w // kernel.LANES, kernel.LANES)
    edges = [jnp.take(keys, bg.src_t, axis=0), bg.dstloc_t, streams.mask,
             streams.w]
    if streams.hub is not None:
        edges.append(streams.hub)
    edge = pl.BlockSpec((None, None, w // kernel.LANES, kernel.LANES),
                        lambda j, i: (j, i, 0, 0))
    out = pl.pallas_call(
        kernel._relax_sweep_kernel,
        grid=(s, nr),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [edge] * len(edges),
        out_specs=pl.BlockSpec((None, None, 1, bg.block_v),
                               lambda j, i: (j, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((s, nr, 1, bg.block_v), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(params, *(x.reshape(lanes) for x in edges))
    out = kernel._reduce_rows(out.reshape(s, nr, bg.block_v),
                              bg.rowblk_t if bg.chunked else None, bg.nb,
                              jnp.asarray(inf, jnp.int32))
    return out.reshape(-1)[:bg.n]


def relax_sweep_sorted(keys: jax.Array, sg: SortedGraph,
                       edge_mask: jax.Array, step, inf, clear_bit=0,
                       hub: jax.Array | None = None,
                       w: jax.Array | None = None,
                       streams: SweepStreams | None = None) -> jax.Array:
    """The `sorted` impl of the same sweep: compiled XLA everywhere.

    Identical math to `relax_sweep` over the identical edge multiset —
    gather, weighted saturating extend, mask, min-reduce by destination —
    so results are bit-identical to both the kernel path and the jnp
    reference (`tests/test_kernel_tuning.py` pins all three). The
    reduction is a `segment_min` over the destination-sorted slots with
    `indices_are_sorted=True`, and only the occupied slots participate.
    `streams` (`sg.streams`) stands for `edge_mask`, `w` and `hub`, as
    in `relax_sweep`.
    """
    if streams is None:
        streams = sg.streams(edge_mask, w, hub)
    gathered = jnp.take(keys, sg.src_s, axis=0)
    sw = step if streams.w is None else step * streams.w
    s = gathered + sw
    cand = jnp.minimum(jnp.where(s < 0, inf, s), inf)
    if streams.hub is not None:
        cand = jnp.where(streams.hub, cand & ~jnp.int32(clear_bit), cand)
    cand = jnp.where(streams.mask, cand, inf)
    out = jax.ops.segment_min(cand, sg.dst_s, num_segments=sg.n,
                              indices_are_sorted=True)
    return jnp.minimum(out, inf)   # empty segments fill with int32-max


def frontier_or(words: jax.Array, bg: BlockedGraph,
                dst_t: jax.Array) -> jax.Array:
    """The OR sweep of packed frontier words [W, V] on the tiled graph.

    `dst_t` is `bg.masked_dst(edge_mask)`, which stays fixed across the
    waves of one search. Runs interpret-mode Pallas off-TPU, like
    `relax_sweep`, so parity tests exercise the kernel that runs on TPU.
    """
    return kernel.frontier_or_pallas(
        words, bg.src_t, dst_t, bg.rowblk_t, bg.n, bg.block_v, bg.nb,
        interpret=jax.default_backend() != "tpu")
