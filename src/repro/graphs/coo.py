"""Padded COO graph structures for batch-dynamic graphs on TPU.

Shapes are static: a graph owns a fixed edge *capacity*; edges live in slots
with a validity mask. Batch updates toggle validity (deletions) and fill free
slots (insertions), so a single compiled executable serves every batch.

Undirected edges are stored as both directions in adjacent slot pairs
(slot 2k holds u->v, slot 2k+1 holds v->u), which keeps insertion/deletion
of the two directions in lockstep.

The metric is weighted (DESIGN.md §8): every slot carries a non-negative
int32 weight in `Graph.w`, kept in lockstep with src/dst/valid by
`from_edges`/`apply_batch`/`grow`. Real edges have weight in [1, INF_D];
free/padding slots carry 0 (never read — sweeps mask them out). The
unweighted metric is exactly the `w ≡ 1` special case. Batches support a
third op besides insert/delete: *re-weight* (`OP_REW`), which updates the
weight of an existing edge in place — no slot churn, no capacity use.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Large-but-safe int32 infinity for distances (headroom for +w relaxations).
INF_D = jnp.int32(1 << 28)

# Batch-update op codes (make_batch third tuple element; a bool is_del from
# the legacy 3-tuple format maps onto OP_INS/OP_DEL unchanged).
OP_INS, OP_DEL, OP_REW = 0, 1, 2


class CapacityError(ValueError):
    """A graph's static slots cannot hold the requested edges/vertices.

    Raised by `from_edges` at build time and by the pre-growth check of
    `core/growth.ensure_capacity` *before* any device dispatch — the
    alternative is `apply_batch` silently clobbering its last free slot
    pair, surfacing later as a wrong answer or a shape error from deep
    inside jit. Carries the numbers a caller needs to grow (or to size a
    fresh build): the tick that overflowed (None outside a serve stream),
    the current and required edge capacities (slot pairs), and the current
    and required vertex counts.
    """

    def __init__(self, message: str, *, tick: int | None = None,
                 capacity: int | None = None,
                 required_capacity: int | None = None,
                 n: int | None = None, required_n: int | None = None):
        super().__init__(message)
        self.tick = tick
        self.capacity = capacity
        self.required_capacity = required_capacity
        self.n = n
        self.required_n = required_n


@partial(jax.tree_util.register_dataclass,
         data_fields=("src", "dst", "valid", "w"), meta_fields=("n",))
@dataclasses.dataclass(frozen=True)
class Graph:
    """Padded undirected graph in COO form (both directions stored)."""
    src: jax.Array   # int32[2*cap]
    dst: jax.Array   # int32[2*cap]
    valid: jax.Array # bool[2*cap]
    w: jax.Array     # int32[2*cap] edge weight; 0 on free/padding slots
    n: int           # static vertex count

    @property
    def capacity(self) -> int:
        return self.src.shape[0] // 2

    def num_edges(self) -> jax.Array:
        return jnp.sum(self.valid) // 2


@partial(jax.tree_util.register_dataclass,
         data_fields=("src", "dst", "is_del", "valid", "w", "is_rew"),
         meta_fields=())
@dataclasses.dataclass(frozen=True)
class BatchUpdate:
    """A padded batch of edge updates (insert / delete / re-weight)."""
    src: jax.Array    # int32[U]
    dst: jax.Array    # int32[U]
    is_del: jax.Array # bool[U]
    valid: jax.Array  # bool[U]  (padding mask)
    w: jax.Array      # int32[U] weight (insert: new edge's; rew: new value)
    is_rew: jax.Array # bool[U]  re-weight op (neither insert nor delete)


def from_edges(n: int, edges: np.ndarray, capacity: int) -> Graph:
    """Build a padded Graph from a numpy edge array (undirected).

    `edges` is [m, 2] (unit weights) or [m, 3] with an int weight column.
    """
    edges = np.asarray(edges, dtype=np.int32)
    edges = edges.reshape(-1, 2) if (edges.ndim < 2 or edges.shape[1] == 2) \
        else edges.reshape(-1, 3)
    m = edges.shape[0]
    if m > capacity:
        raise CapacityError(f"{m} edges exceed capacity {capacity}",
                            capacity=capacity, required_capacity=m, n=n)
    src = np.zeros(2 * capacity, np.int32)
    dst = np.zeros(2 * capacity, np.int32)
    valid = np.zeros(2 * capacity, bool)
    w = np.zeros(2 * capacity, np.int32)
    src[0:2 * m:2], dst[0:2 * m:2] = edges[:, 0], edges[:, 1]
    src[1:2 * m:2], dst[1:2 * m:2] = edges[:, 1], edges[:, 0]
    ew = edges[:, 2] if edges.shape[1] == 3 else np.ones(m, np.int32)
    w[0:2 * m:2] = ew
    w[1:2 * m:2] = ew
    valid[:2 * m] = True
    return Graph(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
                 jnp.asarray(w), n)


def grow(g: Graph, *, capacity: int | None = None,
         n: int | None = None) -> Graph:
    """Return `g` with larger static slots: the same edge set, more room.

    New edge slots are free (valid False, src/dst zeroed — the same
    convention `from_edges` uses for its padding), and a larger `n` only
    widens the vertex id space; no existing slot moves, so the grown graph
    is the *same* graph. Shrinking is refused: slots past the new capacity
    could hold live edges, and vertex ids past the new n could be
    referenced by them.
    """
    capacity = g.capacity if capacity is None else capacity
    n = g.n if n is None else n
    if capacity < g.capacity or n < g.n:
        raise ValueError(
            f"grow cannot shrink: capacity {g.capacity}->{capacity}, "
            f"n {g.n}->{n}")
    pad = 2 * (capacity - g.capacity)
    if pad == 0:
        return Graph(g.src, g.dst, g.valid, g.w, n)
    return Graph(jnp.concatenate([g.src, jnp.zeros((pad,), jnp.int32)]),
                 jnp.concatenate([g.dst, jnp.zeros((pad,), jnp.int32)]),
                 jnp.concatenate([g.valid, jnp.zeros((pad,), bool)]),
                 jnp.concatenate([g.w, jnp.zeros((pad,), jnp.int32)]), n)


def _deletion_hits(g: Graph, b: BatchUpdate) -> jax.Array:
    """bool[E2]: slots whose undirected canonical (min, max) endpoints
    match a valid deletion row of `b`. The [E2, U] compare fuses into the
    reduction under jit; run eagerly it would materialize E2·U bools
    (8 GiB at 2^23 slots and U = 1024)."""
    del_mask_u = b.is_del & b.valid
    g_lo = jnp.minimum(g.src, g.dst)
    g_hi = jnp.maximum(g.src, g.dst)
    b_lo = jnp.where(del_mask_u, jnp.minimum(b.src, b.dst), -1)
    b_hi = jnp.where(del_mask_u, jnp.maximum(b.src, b.dst), -1)
    return jnp.any((g_lo[:, None] == b_lo[None, :])
                   & (g_hi[:, None] == b_hi[None, :]), axis=1)


@jax.jit
def _freed_slots(g: Graph, b: BatchUpdate) -> jax.Array:
    return jnp.sum(_deletion_hits(g, b) & g.valid)


def batch_requirements(g: Graph, b: BatchUpdate) -> tuple[int, int]:
    """Host-side (required_capacity, required_n) to apply `b` to `g`.

    `required_capacity` is exact for `apply_batch`'s semantics: occupied
    slot pairs, minus the pairs the batch's own deletions free (deletions
    are processed before insertions, and the deletion match below is the
    same undirected canonical-endpoint match `apply_batch` uses — so a
    batch is rejected/grown-for iff it genuinely would not fit), plus the
    batch's valid insertions. `required_n` is one past the largest vertex
    id any valid update row touches. Costs one O(E·U) device compare +
    two scalar syncs per call — negligible next to the update it gates.
    """
    is_del = np.asarray(b.is_del)
    is_rew = np.asarray(b.is_rew)
    valid = np.asarray(b.valid)
    # Re-weights update a live slot in place — they consume no capacity.
    n_ins = int(((~is_del) & (~is_rew) & valid).sum())
    occupied_pairs = int(jnp.sum(g.valid)) // 2
    freed_pairs = int(_freed_slots(g, b)) // 2
    ids = np.concatenate([np.asarray(b.src)[valid], np.asarray(b.dst)[valid]])
    required_n = int(ids.max()) + 1 if ids.size else 0
    return occupied_pairs - freed_pairs + n_ins, required_n


def make_batch(updates, pad_to: int | None = None) -> BatchUpdate:
    """updates: iterable of (u, v, op) or (u, v, op, weight).

    `op` is OP_INS/OP_DEL/OP_REW (a bool is_del from the legacy 3-tuple
    format coerces to OP_DEL/OP_INS). `weight` defaults to 1; it is the
    inserted edge's weight for OP_INS and the new value for OP_REW
    (ignored for OP_DEL). Pads to `pad_to` slots.
    """
    ups = list(updates)
    u_count = len(ups)
    size = pad_to or max(u_count, 1)
    src = np.zeros(size, np.int32)
    dst = np.zeros(size, np.int32)
    is_del = np.zeros(size, bool)
    valid = np.zeros(size, bool)
    w = np.ones(size, np.int32)
    is_rew = np.zeros(size, bool)
    for i, up in enumerate(ups):
        a, b, op = up[0], up[1], int(up[2])
        src[i], dst[i], valid[i] = a, b, True
        is_del[i] = op == OP_DEL
        is_rew[i] = op == OP_REW
        if len(up) > 3:
            w[i] = int(up[3])
    return BatchUpdate(jnp.asarray(src), jnp.asarray(dst),
                       jnp.asarray(is_del), jnp.asarray(valid),
                       jnp.asarray(w), jnp.asarray(is_rew))


@jax.jit
def apply_batch(g: Graph, b: BatchUpdate) -> Graph:
    """Apply a batch update, returning G'.

    Deletions: clear validity of matching slots (both directions).
    Re-weights: set the weight of matching live slots in place (no slot
    churn — a re-weight of a non-edge is a no-op, like an unmatched
    deletion).
    Insertions: write both directions (src/dst/weight) into the first
    free slot pair.
    Invalid (padded) updates are ignored.

    Jitted: the body is ~25 elementwise/scatter ops, and un-fused their
    per-op dispatch cost (~15ms on a 1-core host) dwarfs the actual work
    for small batches — it was the floor under every small-footprint
    tick. One compile per (capacity, batch-pad) shape pair.
    """
    # --- deletions ---------------------------------------------------------
    hit = _deletion_hits(g, b)
    valid = g.valid & ~hit
    # Freed slots drop their weight with their validity, so a graph's slot
    # arrays are a pure function of its update history (split-batch
    # reproducibility), never of stale weights.
    w = jnp.where(hit, 0, g.w)

    # --- re-weights --------------------------------------------------------
    # Same canonical-endpoint match against the *pre-insertion* slots,
    # gated on post-deletion validity: a re-weight targets an edge that is
    # live in G after this batch's deletions, and both direction slots of
    # the pair update together.
    rew_mask_u = b.is_rew & b.valid
    g_lo = jnp.minimum(g.src, g.dst)
    g_hi = jnp.maximum(g.src, g.dst)
    r_lo = jnp.where(rew_mask_u, jnp.minimum(b.src, b.dst), -1)
    r_hi = jnp.where(rew_mask_u, jnp.maximum(b.src, b.dst), -1)
    rhit = ((g_lo[:, None] == r_lo[None, :])
            & (g_hi[:, None] == r_hi[None, :]))             # [E2, U]
    rrow = jnp.argmax(rhit, axis=1)                          # first match
    rany = jnp.any(rhit, axis=1) & valid
    w = jnp.where(rany, b.w[rrow], w)

    # --- insertions --------------------------------------------------------
    ins_mask = (~b.is_del) & (~b.is_rew) & b.valid
    u_slots = b.src.shape[0]
    # Free slot *pairs* (even index free & odd index free).
    pair_free = ~(valid[0::2] | valid[1::2])
    # Rank of each insertion among valid insertions.
    ins_rank = jnp.cumsum(ins_mask) - 1
    # The k-th free pair index, for k = 0..U-1.
    free_pair_idx = jnp.nonzero(pair_free, size=u_slots,
                                fill_value=pair_free.shape[0] - 1)[0]
    pair_for_ins = free_pair_idx[jnp.clip(ins_rank, 0, u_slots - 1)]
    even = 2 * pair_for_ins
    odd = even + 1
    # Non-insert rows scatter to an out-of-bounds index, which JAX drops —
    # never to slot 0, where duplicate writes would clobber real inserts.
    oob = jnp.int32(g.src.shape[0])
    safe_even = jnp.where(ins_mask, even, oob)
    safe_odd = jnp.where(ins_mask, odd, oob)
    src = g.src.at[safe_even].set(b.src, mode="drop")
    dst = g.dst.at[safe_even].set(b.dst, mode="drop")
    src = src.at[safe_odd].set(b.dst, mode="drop")
    dst = dst.at[safe_odd].set(b.src, mode="drop")
    valid = valid.at[safe_even].set(True, mode="drop")
    valid = valid.at[safe_odd].set(True, mode="drop")
    w = w.at[safe_even].set(b.w, mode="drop")
    w = w.at[safe_odd].set(b.w, mode="drop")
    return Graph(src, dst, valid, w, g.n)


@jax.jit
def resolve_seed_weights(g_old: Graph, b: BatchUpdate) -> BatchUpdate:
    """Replace `b.w` with the *seed* weight of each row against G (pre-update).

    The BatchHL searches seed affected sets from the changed edge's weight
    (DESIGN.md §8): for an insertion that is the new edge's weight; for a
    deletion it is the removed edge's weight *in G* (the distances that may
    have used it); for a re-weight it is min(old, new) — the smaller weight
    seeds a smaller key, which marks a superset of the vertices affected by
    either direction of the change (repair then recomputes exactly).
    One [U, E2] canonical-endpoint compare, the same cost as
    `apply_batch`'s deletion match. Padding rows get weight 1, and
    unmatched delete/re-weight rows fall back to weight 1 (they are
    no-ops in `apply_batch` anyway).

    Jitted, so that a call outside any program (the pipelined update's)
    fuses the compare into its max-reduction over E2, as it is inside
    `batchhl_update`: run op by op it materialises [U, E2] boolean arrays,
    6 GiB each at U = 1024 and 6.4M edge slots, more than a chip has
    beside the labelling.
    """
    need_old = (b.is_del | b.is_rew) & b.valid
    g_lo = jnp.minimum(g_old.src, g_old.dst)
    g_hi = jnp.maximum(g_old.src, g_old.dst)
    b_lo = jnp.where(need_old, jnp.minimum(b.src, b.dst), -1)
    b_hi = jnp.where(need_old, jnp.maximum(b.src, b.dst), -1)
    m = ((b_lo[:, None] == g_lo[None, :])
         & (b_hi[:, None] == g_hi[None, :])
         & g_old.valid[None, :])                              # [U, E2]
    w_old = jnp.max(jnp.where(m, g_old.w[None, :], 0), axis=1)
    w_old = jnp.where(w_old == 0, 1, w_old)                   # unmatched
    w_eff = jnp.where(b.is_del, w_old,
                      jnp.where(b.is_rew, jnp.minimum(w_old, b.w), b.w))
    return dataclasses.replace(b, w=jnp.where(b.valid, w_eff, 1)
                               .astype(jnp.int32))


def to_numpy_adj(g: Graph) -> dict[int, set[int]]:
    """Adjacency dict for the oracle / tests (host only)."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    valid = np.asarray(g.valid)
    adj: dict[int, set[int]] = {v: set() for v in range(g.n)}
    for s, d, ok in zip(src, dst, valid):
        if ok:
            adj[int(s)].add(int(d))
    return adj


def to_numpy_wadj(g: Graph) -> dict[int, dict[int, int]]:
    """Weighted adjacency dict {u: {v: w}} for the Dijkstra oracle (host).

    Parallel slots for the same arc (should not occur via `apply_batch`,
    which deduplicates by canonical endpoints) keep the minimum weight.
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    valid = np.asarray(g.valid)
    w = np.asarray(g.w)
    adj: dict[int, dict[int, int]] = {v: {} for v in range(g.n)}
    for s, d, ok, wi in zip(src, dst, valid, w):
        if ok:
            row = adj[int(s)]
            d = int(d)
            row[d] = min(row[d], int(wi)) if d in row else int(wi)
    return adj
