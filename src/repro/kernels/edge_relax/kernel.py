"""Blocked edge-relaxation kernel: the BatchHL wave hot loop.

    cand[v] = min over edges (u, v)   extend(keys[u])         (then min w/ keys)

where extend is the paper's path-extension operator on encoded keys
(see core/labelling.py): add `step`, clamp at `inf`, and clear `clear_bit`
when the destination is a landmark hub. With clear_bit=0 this degenerates to
plain min-plus relaxation (BFS / Algo-2 waves); with (step=2, clear_bit=1)
it is key2_extend (construction / Algo-4 repair) and with (step=4,
clear_bit=2) it is key4_extend (Algo-3 improved search).

TPU adaptation of the paper's adjacency-list traversal: edges are pre-tiled
by destination block (CSR-style reordering done once per graph topology,
amortized over all waves of all batches), so each grid step owns a disjoint
slice of the output vertices — no cross-block write races, no atomics.
Within a block the kernel gathers source keys from the VMEM-resident key
plane (per-device vertex shard: V_local ≤ ~1M keys = 4 MB, fits VMEM) and
scatter-mins into the local [BV] output tile. The per-edge validity mask is
re-derived on device every sweep (validity churns with every batch update),
while the src/dstloc tiling itself is rebuilt only when topology slots
change — the contract `core/engine.py` enforces.

The tiling is *shard-aware*: tile arrays carry a leading vertex-shard axis
[S, NB, BE] (S contiguous block_v-aligned slices of the vertex range, each
with its own destination blocks and its own slice of the slot permutation)
and the launch grid is (S, NB). Destination blocks never straddle a shard
boundary, so the per-block edge groups — and therefore the per-block
min-reductions — are identical for every S: results are bit-identical to
the S=1 tiling, which is the degenerate single-shard case. This is what
lets the kernel run inside `shard_map` bodies (`core/shard.py`) and, at
scale, lets each mesh device launch over its local slice only.

Working set per grid step: keys (full shard) + BE·3·4 B edge slice +
2·BV·4 B hub/out tiles. For BV=512, BE=4096: ≈ 64 KB on top of the keys.

This kernel regime is the sparse/SpMM family (kernel_taxonomy §B.3/§B.11):
gather → elementwise → segment-reduce. The MXU is idle; the roofline is
HBM-bandwidth on the edge slices + VMEM gather throughput.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF32 = 1 << 29  # plain int: pallas kernels must not capture traced constants
LANES = 128      # TPU vector lane width: tile rows are padded to a multiple


def _relax_kernel(keys_ref, src_ref, dstloc_ref, valid_ref, step_ref, o_ref):
    keys = keys_ref[...]          # [V] int32 (full shard)
    src = src_ref[0, 0]           # [BE]
    dstloc = dstloc_ref[0, 0]     # [BE] local dst in [0, BV)
    valid = valid_ref[0, 0]       # [BE] int32 mask
    step = step_ref[0]

    gathered = jnp.take(keys, src, axis=0)
    s = gathered + step
    cand = jnp.minimum(jnp.where(s < 0, INF32, s), INF32)
    cand = jnp.where(valid != 0, cand, INF32)
    out = jnp.full((o_ref.shape[-1],), INF32, jnp.int32)
    out = out.at[dstloc].min(cand)
    o_ref[...] = out[None, None, :]


def _relax_sweep_kernel(params_ref, keys_ref, dstloc_ref, mask_ref, w_ref,
                        *refs):
    """Generalized sweep over one tile row: weighted extend (step·w /
    saturate-at-inf / hub bit-clear) + mask, then a destination min.

    Edge streams arrive as [C, 128] lane rows (C = BE/128); the source
    keys and destination hub flags were gathered per edge in XLA, since
    Mosaic lowers no 1-D gather or scatter. The scatter-min becomes a
    broadcast compare-and-min: every 128-edge lane row is compared with
    the [BV] destination ids laid along sublanes and min-folded into a
    [BV, 128] accumulator, which one transpose + sublane min reduces to
    the [1, BV] output tile. `refs` is (hub_ref, o_ref), or (o_ref,) for
    a sweep without hub bit-clearing.
    """
    *hub_ref, o_ref = refs
    step = params_ref[0]
    inf = params_ref[1]
    clear = params_ref[2]
    bv = o_ref.shape[-1]
    v_ids = jax.lax.broadcasted_iota(jnp.int32, (bv, LANES), 0)

    def fold(c, acc):
        row = pl.ds(c, 1)
        # Saturating weighted extend: keys and step·w are both
        # non-negative (step ≤ 4, w ≤ INF_D keeps the product in range),
        # so the int32 sum overflows iff it wraps negative — clamp those
        # to inf rather than letting a near-inf key pass a max-weight
        # edge as a small key.
        s = keys_ref[row, :] + step * w_ref[row, :]
        cand = jnp.minimum(jnp.where(s < 0, inf, s), inf)
        if hub_ref:
            cand = jnp.where(hub_ref[0][row, :] != 0, cand & ~clear, cand)
        cand = jnp.where(mask_ref[row, :] != 0, cand, inf)     # [1, 128]
        hit = v_ids == dstloc_ref[row, :]                        # [BV, 128]
        return jnp.minimum(acc, jnp.where(hit, cand, inf))

    acc = jax.lax.fori_loop(0, keys_ref.shape[0], fold,
                            jnp.full((bv, LANES), inf, jnp.int32))
    o_ref[...] = jnp.min(acc.T, axis=0, keepdims=True)


def block_edges_topology(src: np.ndarray, dst: np.ndarray, keep: np.ndarray,
                         n: int, block_v: int, block_e: int | None = None):
    """Host-side tiling: group the kept edge slots by destination block.

    Returns (src_t [NR, W], dstloc_t [NR, W], perm_t [NR, W],
    slot_t [NR, W], rowblk [NR], block_v). `perm_t` maps each tile slot
    back to its original edge index so per-sweep masks (validity churn,
    repair boundary/interior masks) can be re-tiled on device with one
    gather; `slot_t` is 0 on padding slots. Done once per graph topology.

    Each row holds at most BE edges and is padded to the lane width
    W = ceil(BE / 128)·128, the row shape the TPU kernel tiles. A block
    with more than BE edges is *chunked* into ceil(count/BE) rows —
    `rowblk[r]` names the destination block row r feeds, rows of one
    block are consecutive, and every block keeps at least one row
    (possibly all-padding) so reducing rows by `rowblk` yields a value
    for every block. `block_e` sets BE; without it BE is the largest
    per-block count, capped at the mean per-block count rounded up to
    128. On power-law graphs that cap keeps the hub block from widening
    every row: total slots stay within NB·(mean + 128) + E instead of
    NB·max-block.
    """
    keep = np.asarray(keep, bool)
    idx = np.flatnonzero(keep).astype(np.int64)
    src_k, dst_k = src[idx], dst[idx]
    nb = -(-n // block_v)
    order = np.argsort(dst_k // block_v, kind="stable")
    src_k, dst_k, idx = src_k[order], dst_k[order], idx[order]
    counts = np.bincount(dst_k // block_v, minlength=nb)
    if block_e:
        be = block_e
    else:
        mean = -(-src_k.size // nb) if nb else 0
        be = max(min(int(counts.max() if counts.size else 0),
                     -(-mean // LANES) * LANES), 8)
    width = -(-be // LANES) * LANES
    rows_per_block = np.maximum(-(-counts // be), 1)
    nr = int(rows_per_block.sum())
    src_t = np.zeros((nr, width), np.int32)
    dst_t = np.zeros((nr, width), np.int32)
    perm_t = np.zeros((nr, width), np.int32)
    slot_t = np.zeros((nr, width), np.int32)
    rowblk = np.repeat(np.arange(nb, dtype=np.int32),
                       rows_per_block).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    row_starts = np.concatenate([[0], np.cumsum(rows_per_block)])
    if src_k.size:
        # Each kept edge lands at (row_starts[block] + within // BE,
        # within % BE) where `within` is its rank inside its block —
        # one vectorized scatter (this runs every insert tick on the
        # serving path, so no per-block python loop).
        blk = dst_k // block_v
        within = np.arange(src_k.size, dtype=np.int64) - starts[blk]
        r = row_starts[blk] + within // be
        c = within % be
        src_t[r, c] = src_k
        dst_t[r, c] = dst_k - blk * block_v
        perm_t[r, c] = idx
        slot_t[r, c] = 1
    return src_t, dst_t, perm_t, slot_t, rowblk, block_v


def aligned_vertex_count(n: int, block_v: int, shards: int) -> int:
    """Smallest vertex count >= n that tiles cleanly: a multiple of
    block_v · shards, so every destination block is full-width and
    `shard_tiling` splits the block axis into `shards` equal groups with
    no all-padding blocks. The growth policy (`core/growth.py`) rounds
    grown vertex counts up to this so a grown tiling has the same shape
    invariants as a fresh one at the same size.
    """
    if n < 1 or block_v < 1 or shards < 1:
        raise ValueError(
            f"need positive n/block_v/shards, got {n}/{block_v}/{shards}")
    unit = block_v * shards
    return -(-n // unit) * unit


def shard_tiling(shards: int, nb: int, rowblk: np.ndarray,
                 *tiles: np.ndarray):
    """Split [NR, BE] tile rows into `shards` contiguous vertex shards.

    Shard s owns destination blocks [s·NB_loc, (s+1)·NB_loc) — and every
    tile row feeding them. Block boundaries are block_v-aligned, so no
    destination block straddles a shard, row *contents* are untouched, and
    flattening the per-shard block order recovers the exact unsharded
    order (padding blocks all land past the last real block, past every
    real vertex). Per-block reductions — and therefore sweep results —
    are bit-identical for every S.

    Returns (rowblk_t [S, NR_loc] of *local* block ids, nb_loc,
    *tiles [S, NR_loc, BE]). NR_loc is the largest shard's row count,
    rounded up by at most 1/16 of itself. Shards with fewer rows pad with
    all-zero rows mapped to the shard's last local block (keeps each
    shard's rowblk sorted — the row→block reduction relies on it);
    padding rows have slot_t=0 everywhere, so they only contribute `inf`.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    nb_loc = max(-(-nb // shards), 1)
    shard_of = rowblk // nb_loc                       # rows sorted by block,
    row_counts = np.bincount(shard_of, minlength=shards)  # so shards are
    nr_loc = max(int(row_counts.max()), 1)                # contiguous runs
    # Round NR_loc up by at most 1/16: insert churn that adds a few rows
    # then keeps the tile shapes, and the jitted sweeps their executables.
    unit = 1 << max(nr_loc.bit_length() - 5, 0)
    nr_loc = -(-nr_loc // unit) * unit
    row_starts = np.concatenate([[0], np.cumsum(row_counts)])
    be = tiles[0].shape[1]
    rowblk_t = np.full((shards, nr_loc), nb_loc - 1, np.int32)
    out = [np.zeros((shards, nr_loc, be), t.dtype) for t in tiles]
    for s in range(shards):
        lo, hi = int(row_starts[s]), int(row_starts[s + 1])
        m = hi - lo
        rowblk_t[s, :m] = rowblk[lo:hi] - s * nb_loc
        for o, t in zip(out, tiles):
            o[s, :m] = t[lo:hi]
    return (rowblk_t, nb_loc) + tuple(out)


def _reduce_rows(out: jax.Array, rowblk_t: jax.Array | None, nb: int | None,
                 inf) -> jax.Array:
    """Fold per-row partial mins [S, NR, BV] into per-block mins [S, NB, BV].

    Rows of one destination block are consecutive and each block has at
    least one row, so a sorted segment-min per shard recovers exactly the
    per-block reduction an unchunked tiling computes — min-of-mins over
    any grouping of the same integer multiset. `rowblk_t=None` means the
    tiling was not chunked (NR = NB, identity mapping): pass through.
    Padding blocks (no rows at all only happens past `nb`) clamp to `inf`.
    """
    if rowblk_t is None:
        return out
    def one(o, rb):
        return jax.ops.segment_min(o, rb, num_segments=nb,
                                   indices_are_sorted=True)
    return jnp.minimum(jax.vmap(one)(out, rowblk_t), inf)


@functools.partial(jax.jit, static_argnames=("n", "block_v", "nb",
                                             "interpret"))
def edge_relax_pallas(keys: jax.Array, src_t: jax.Array, dstloc_t: jax.Array,
                      valid_t: jax.Array, step: jax.Array, n: int,
                      block_v: int, interpret: bool = True,
                      rowblk_t: jax.Array | None = None,
                      nb: int | None = None) -> jax.Array:
    """keys [V] int32 + tiled edges [S, NR, BE] → cand [V] int32.

    `rowblk_t`/`nb` describe a block_e-chunked tiling (see
    `block_edges_topology`); omitted, rows are blocks (NR = NB).
    """
    s, nr, be = src_t.shape
    step_arr = jnp.full((1,), step, jnp.int32)

    out = pl.pallas_call(
        _relax_kernel,
        grid=(s, nr),
        in_specs=[
            pl.BlockSpec(keys.shape, lambda j, i: (0,) * keys.ndim),
            pl.BlockSpec((1, 1, be), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, 1, be), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, 1, be), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1,), lambda j, i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_v), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, nr, block_v), jnp.int32),
        interpret=interpret,
    )(keys, src_t, dstloc_t, valid_t, step_arr)
    out = _reduce_rows(out, rowblk_t, nb, INF32)
    return out.reshape(-1)[:n]


def _frontier_or_kernel(rowblk_ref, words_ref, dst_ref, o_ref):
    """OR sweep over one tile row: each edge's packed source word lands
    on its destination, `acc |= where(hit, word, 0)` in place of the
    relaxation's compare-and-min.

    Edge streams arrive as [C, 128] lane rows, as in `_relax_sweep_kernel`;
    masked-off and padding slots carry destination -1 and never hit. The
    [BV, 128] accumulator is transposed and OR-folded over sublanes to
    [8, BV]; the caller ORs the last 8 together. Rows of one destination
    block are consecutive grid steps on the same output tile (the
    scalar-prefetched `rowblk_ref` names each row's block), so the first
    row of a block writes the tile and the others OR into it.
    """
    j, i = pl.program_id(1), pl.program_id(2)
    bv = o_ref.shape[-1]
    v_ids = jax.lax.broadcasted_iota(jnp.int32, (bv, LANES), 0)

    def fold(c, acc):
        row = pl.ds(c, 1)
        hit = v_ids == dst_ref[row, :]                           # [BV, 128]
        return acc | jnp.where(hit, words_ref[row, :], 0)

    acc = jax.lax.fori_loop(0, dst_ref.shape[0], fold,
                            jnp.zeros((bv, LANES), jnp.uint32))
    part = acc.T                                                 # [128, BV]
    while part.shape[0] > 8:
        half = part.shape[0] // 2
        part = part[:half] | part[half:]
    flat = j * pl.num_programs(2) + i
    first = (i == 0) | (rowblk_ref[flat]
                        != rowblk_ref[jnp.maximum(flat - 1, 0)])

    @pl.when(first)
    def _():
        o_ref[...] = part

    @pl.when(jnp.logical_not(first))
    def _():
        o_ref[...] = o_ref[...] | part


@functools.partial(jax.jit, static_argnames=("n", "block_v", "nb",
                                             "interpret"))
def frontier_or_pallas(words: jax.Array, src_t: jax.Array, dst_t: jax.Array,
                       rowblk_t: jax.Array, n: int, block_v: int, nb: int,
                       interpret: bool = True) -> jax.Array:
    """Packed frontier words [W, V] uint32 + tiled edges [S, NR, BE] →
    [W, V] uint32: out[:, v] = OR of words[:, u] over the slots (u, v).

    `dst_t` is the destination local to its block, -1 on slots the
    caller masks off (computed once per graph, not per wave); `rowblk_t`
    [S, NR] names each row's local destination block (the identity on
    an unchunked tiling). The grid walks (word, vertex shard, tile row)
    and chunked rows fold into their block inside the kernel. Like
    `ops.relax_sweep_pallas`, the per-edge source gather runs in XLA; here
    it is one packed word per slot instead of one key per plane.
    """
    s, nr, w = src_t.shape
    if w % LANES:
        raise ValueError(f"tile rows must be a multiple of {LANES} wide, "
                         f"got {w} (see block_edges_topology)")
    nw = words.shape[0]
    lanes = (s, nr, w // LANES, LANES)
    gathered = jnp.take(words, src_t, axis=1).reshape((nw,) + lanes)
    out = pl.pallas_call(
        _frontier_or_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nw, s, nr),
            in_specs=[
                pl.BlockSpec((None, None, None, w // LANES, LANES),
                             lambda q, j, i, rb: (q, j, i, 0, 0)),
                pl.BlockSpec((None, None, w // LANES, LANES),
                             lambda q, j, i, rb: (j, i, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, None, None, 8, block_v),
                lambda q, j, i, rb: (q, j, rb[j * nr + i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nw, s, nb, 8, block_v), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rowblk_t.reshape(-1), gathered, dst_t.reshape(lanes))
    out = jax.lax.reduce(out, np.uint32(0), jax.lax.bitwise_or, (3,))
    return out.reshape(nw, -1)[:, :n]
