"""Directed-graph BatchHL (paper §6, Table 6).

Two labelling planes are maintained:
  * forward  L_f[r, v] = δ(r → v)  — wave relaxation along arcs,
  * backward L_b[r, v] = δ(v → r)  — relaxation along reversed arcs,
with forward/backward highways H_f = H_bᵀ. A query (s, t) combines
    d⊤ = min_{i,j}  L_b[i, s] + H_f[i, j] + L_f[j, t]
with a distance-bounded directed bidirectional search (forward from s,
backward from t) on G[V \\ R].

Updates: an arc (a→b) only creates/destroys paths entering through b on the
forward plane (and through a on the backward plane), so the anchor is fixed
per plane — a one-sided specialization of the paper's anchor rule. Batch
search/repair then run unchanged on the corresponding edge orientation.

Storage: one padded arc table (src, dst, valid) holds each arc once; the
backward plane relaxes it with src/dst swapped. `apply_batch_directed`
matches deletions exactly (no undirected canonicalization).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.coo import Graph, BatchUpdate, INF_D
from repro.core.labelling import (
    HighwayLabelling, INF_KEY2, INF_KEY4, key2_dist, key2_hub,
    key4_from_key2, key4_extend, key4_beta,
)
from repro.core.batch import (_per_plane_hub_mask, _fixpoint, batch_repair)
from repro.core.engine import RelaxPlan, relax_sweep
from repro.core.construct import build_labelling


@partial(jax.tree_util.register_dataclass,
         data_fields=("src", "dst", "valid", "w"), meta_fields=("n",))
@dataclasses.dataclass(frozen=True)
class DirectedGraph:
    src: jax.Array    # int32[cap] arc tails
    dst: jax.Array    # int32[cap] arc heads
    valid: jax.Array  # bool[cap]
    w: jax.Array      # int32[cap] arc weight; 0 on free slots
    n: int

    def fwd(self) -> Graph:
        return Graph(self.src, self.dst, self.valid, self.w, self.n)

    def rev(self) -> Graph:
        return Graph(self.dst, self.src, self.valid, self.w, self.n)


def from_arcs(n: int, arcs: np.ndarray, capacity: int) -> DirectedGraph:
    """[m, 2] arcs (unit weight) or [m, 3] (tail, head, weight) rows."""
    arcs = np.asarray(arcs, np.int32)
    arcs = (arcs.reshape(-1, 2) if arcs.ndim < 2 or arcs.shape[1] == 2
            else arcs.reshape(-1, 3))
    m = arcs.shape[0]
    if m > capacity:
        raise ValueError(f"{m} arcs exceed capacity {capacity}")
    src = np.zeros(capacity, np.int32)
    dst = np.zeros(capacity, np.int32)
    valid = np.zeros(capacity, bool)
    w = np.zeros(capacity, np.int32)
    src[:m], dst[:m] = arcs[:, 0], arcs[:, 1]
    w[:m] = arcs[:, 2] if arcs.shape[1] == 3 else 1
    valid[:m] = True
    return DirectedGraph(jnp.asarray(src), jnp.asarray(dst),
                         jnp.asarray(valid), jnp.asarray(w), n)


def apply_batch_directed(g: DirectedGraph, b: BatchUpdate) -> DirectedGraph:
    """Exact-arc deletion + in-place re-weight + free-slot insertion."""
    del_mask = b.is_del & b.valid
    d_src = jnp.where(del_mask, b.src, -1)
    d_dst = jnp.where(del_mask, b.dst, -1)
    hit = jnp.any((g.src[:, None] == d_src[None, :])
                  & (g.dst[:, None] == d_dst[None, :]), axis=1)
    valid = g.valid & ~hit
    w = jnp.where(hit, 0, g.w)   # freed slots drop their weight

    rew_mask = b.is_rew & b.valid
    r_src = jnp.where(rew_mask, b.src, -1)
    r_dst = jnp.where(rew_mask, b.dst, -1)
    rhit = ((g.src[:, None] == r_src[None, :])
            & (g.dst[:, None] == r_dst[None, :]))            # [cap, U]
    rrow = jnp.argmax(rhit, axis=1)
    rany = jnp.any(rhit, axis=1) & valid
    w = jnp.where(rany, b.w[rrow], w)

    ins_mask = (~b.is_del) & (~b.is_rew) & b.valid
    u = b.src.shape[0]
    free_idx = jnp.nonzero(~valid, size=u, fill_value=valid.shape[0] - 1)[0]
    rank = jnp.cumsum(ins_mask) - 1
    slot = free_idx[jnp.clip(rank, 0, u - 1)]
    oob = jnp.int32(g.src.shape[0])
    slot = jnp.where(ins_mask, slot, oob)
    src = g.src.at[slot].set(b.src, mode="drop")
    dst = g.dst.at[slot].set(b.dst, mode="drop")
    valid = valid.at[slot].set(True, mode="drop")
    w = w.at[slot].set(b.w, mode="drop")
    return DirectedGraph(src, dst, valid, w, g.n)


def resolve_seed_weights_directed(g_old: DirectedGraph,
                                  b: BatchUpdate) -> BatchUpdate:
    """Directed twin of `coo.resolve_seed_weights`: exact-arc matching.

    Deletions seed at the arc's pre-update weight, re-weights at
    min(old, new) — the superset-safe seed either way; insertions keep
    the batch's (new) weight.
    """
    need_old = (b.is_del | b.is_rew) & b.valid
    bs = jnp.where(need_old, b.src, -1)
    bd = jnp.where(need_old, b.dst, -1)
    m = ((bs[:, None] == g_old.src[None, :])
         & (bd[:, None] == g_old.dst[None, :])
         & g_old.valid[None, :])                              # [U, cap]
    w_old = jnp.max(jnp.where(m, g_old.w[None, :], 0), axis=1)
    w_old = jnp.where(w_old == 0, 1, w_old)                   # unmatched
    w_eff = jnp.where(b.is_del, w_old,
                      jnp.where(b.is_rew, jnp.minimum(w_old, b.w), b.w))
    return dataclasses.replace(
        b, w=jnp.where(b.valid, w_eff, 1).astype(jnp.int32))


@partial(jax.tree_util.register_dataclass,
         data_fields=("fwd", "bwd"), meta_fields=())
@dataclasses.dataclass(frozen=True)
class DirectedLabelling:
    fwd: HighwayLabelling   # L_f, H_f (distances r → v)
    bwd: HighwayLabelling   # L_b, H_b (distances v → r)


def build_directed_labelling(g: DirectedGraph, landmarks: jax.Array,
                             plan_fwd: RelaxPlan | None = None,
                             plan_bwd: RelaxPlan | None = None
                             ) -> DirectedLabelling:
    """Both planes' labellings. The two arc orientations are two distinct
    topologies to the relaxation engine, so each takes its own plan:
    `plan_fwd` prepared on `g.fwd()`, `plan_bwd` on `g.rev()` (None runs
    the jnp reference, as everywhere)."""
    return DirectedLabelling(build_labelling(g.fwd(), landmarks,
                                             plan=plan_fwd),
                             build_labelling(g.rev(), landmarks,
                                             plan=plan_bwd))


def _directed_search(g_new: Graph, batch_src, batch_dst, batch_e,
                     batch_valid, batch_w, labelling: HighwayLabelling,
                     plan: RelaxPlan | None = None) -> jax.Array:
    """Improved batch search on one plane; anchors fixed at arc heads.

    `batch_e` is the key4 e-flag (deletion-like: deletions and re-weights,
    which can lengthen paths); `batch_w` the per-update seed weight
    (resolved by `resolve_seed_weights_directed`).
    """
    n = g_new.n
    dist_g = labelling.dist
    key2_g = labelling.key2()
    beta = key4_beta(key2_g)
    hub_mask = _per_plane_hub_mask(labelling, n)

    da = dist_g[:, batch_src]                                # [R, U] (pre)
    db = dist_g[:, batch_dst]
    # Arc a→b can only change paths through b; skip if it cannot shorten /
    # was not potentially on a shortest path at its seed weight
    # (superset-safe check; w ≡ 1 recovers the unweighted da+1 <= db).
    nontrivial = ((da + batch_w[None, :] <= db) & (da < INF_D)
                  & batch_valid[None, :])
    key2_pre = jnp.take_along_axis(key2_g, batch_src[None, :].repeat(
        dist_g.shape[0], 0), axis=1)
    k4 = key4_from_key2(key2_pre, batch_e[None, :])
    anchor_is_hub = jnp.take_along_axis(
        hub_mask, batch_dst[None, :].repeat(dist_g.shape[0], 0), axis=1)
    seed_k4 = key4_extend(k4, anchor_is_hub, w=batch_w[None, :])
    seed_k4 = jnp.where(nontrivial, seed_k4, INF_KEY4)

    def scatter_seeds(vals):
        plane = jnp.full((n,), INF_KEY4, jnp.int32)
        return plane.at[batch_dst].min(vals)
    seed = jax.vmap(scatter_seeds)(seed_k4)
    seeded = seed < INF_KEY4

    def plane_fix(seed_p, beta_p, hub_p):
        def sweep(best):
            # key4_extend per arc, routed through the engine: +4, clamp,
            # clear the l-bit at hub heads — same dispatch as the
            # undirected Algo-3 step, so `plan` selects jnp vs Pallas.
            cand = relax_sweep(plan, g_new, best, 4, INF_KEY4,
                               hub=hub_p, clear_bit=2)
            cand = jnp.where(cand <= beta_p, cand, INF_KEY4)
            return jnp.minimum(best, jnp.minimum(cand, seed_p))
        return _fixpoint(sweep, seed_p)[0]

    best = jax.vmap(plane_fix)(seed, beta, hub_mask)
    return seeded | (best < INF_KEY4)


@jax.jit
def batchhl_update_directed(g: DirectedGraph, batch: BatchUpdate,
                            lab: DirectedLabelling,
                            plan_fwd: RelaxPlan | None = None,
                            plan_bwd: RelaxPlan | None = None
                            ) -> tuple[DirectedGraph, DirectedLabelling,
                                       jax.Array]:
    """One directed BatchHL step: both planes searched + repaired.

    Like the undirected `batchhl_update`, plans must be prepared from the
    *post-update* snapshot — `plan_fwd` on `apply_batch_directed(g,
    batch).fwd()`, `plan_bwd` on its `.rev()` (the reversed orientation is
    a distinct topology to the tiler). None runs the jnp reference;
    `tests/test_directed_engine.py` pins backend bit-parity.
    """
    g2 = apply_batch_directed(g, batch)
    batch_res = resolve_seed_weights_directed(g, batch)
    e_flag = batch.is_del | batch.is_rew
    # forward plane: arcs as-is, anchor = head
    aff_f = _directed_search(g2.fwd(), batch.src, batch.dst, e_flag,
                             batch.valid, batch_res.w, lab.fwd, plan_fwd)
    new_f = batch_repair(g2.fwd(), aff_f, lab.fwd, plan_fwd)
    # backward plane: reversed arcs, anchor = tail
    aff_b = _directed_search(g2.rev(), batch.dst, batch.src, e_flag,
                             batch.valid, batch_res.w, lab.bwd, plan_bwd)
    new_b = batch_repair(g2.rev(), aff_b, lab.bwd, plan_bwd)
    return g2, DirectedLabelling(new_f, new_b), aff_f | aff_b


def directed_query(g: DirectedGraph, lab: DirectedLabelling, s: jax.Array,
                   t: jax.Array, max_steps: int = 64,
                   plan_fwd: RelaxPlan | None = None,
                   plan_bwd: RelaxPlan | None = None) -> jax.Array:
    """Exact directed distances d(s → t) for query batches.

    `plan_fwd`/`plan_bwd` route the bidirectional search's frontier
    expansions through the engine (forward waves follow arcs, backward
    waves the reversed orientation); None runs the jnp reference."""
    from repro.core.query import effective_labels
    from repro.core.labelling import landmark_onehot

    lb = effective_labels(lab.bwd)                           # δ(· → r_i)
    lf = effective_labels(lab.fwd)                           # δ(r_j → ·)
    s_lab = jnp.minimum(lb[:, s].T, INF_D)                   # [B, R]
    t_lab = jnp.minimum(lf[:, t].T, INF_D)
    mid = jnp.min(s_lab[:, :, None] + lab.fwd.highway[None, :, :], axis=1)
    d_top = jnp.minimum(jnp.min(mid + t_lab, axis=1), INF_D)

    # bounded directed bidirectional search on G[V \ R]
    n = g.n
    b = s.shape[0]
    blocked = landmark_onehot(lab.fwd.landmarks, n)
    inf = INF_D
    ds = jnp.full((b, n), inf, jnp.int32).at[jnp.arange(b), s].set(0)
    dt = jnp.full((b, n), inf, jnp.int32).at[jnp.arange(b), t].set(0)
    ds = jnp.where(blocked[s][:, None], inf, ds)
    dt = jnp.where(blocked[t][:, None], inf, dt)

    # Weighted termination bound, as in the undirected bounded_bibfs: a
    # path still unaccounted for after ls+lt waves has ≥ ls+lt+1 arcs.
    wmin = jnp.clip(jnp.min(jnp.where(g.valid, g.w, INF_D), initial=INF_D),
                    1, 1 << 20)

    def expand(dist_x, og, plan):
        # One Bellman-Ford wave over the whole plane — the same
        # engine-dispatched primitive (and kernel) as the undirected
        # bounded BiBFS; with w ≡ 1 it reproduces the level-synchronous
        # frontier expansion bit-identically.
        cand = jax.vmap(
            lambda k: relax_sweep(plan, og, k, 1, inf))(dist_x)
        cand = jnp.where(blocked[None, :], inf, cand)
        return jnp.minimum(dist_x, cand)

    def cond(state):
        ds, dt, ls, lt, fs, ft, best, step = state
        return (jnp.any((ls + lt + 1) * wmin < jnp.minimum(best, d_top))
                & (step < max_steps))

    def body(state):
        ds, dt, ls, lt, fs, ft, best, step = state
        exp_s = fs <= ft

        def s_side(a):
            ds, dt, ls, lt, fs, ft = a
            nd = expand(ds, g.fwd(), plan_fwd)
            return nd, dt, ls + 1, lt, jnp.sum(nd != ds), ft

        def t_side(a):
            ds, dt, ls, lt, fs, ft = a
            nd = expand(dt, g.rev(), plan_bwd)
            return ds, nd, ls, lt + 1, fs, jnp.sum(nd != dt)

        ds, dt, ls, lt, fs, ft = jax.lax.cond(exp_s, s_side, t_side,
                                              (ds, dt, ls, lt, fs, ft))
        best = jnp.minimum(best, jnp.min(jnp.minimum(ds + dt, inf), axis=1))
        return ds, dt, ls, lt, fs, ft, best, step + 1

    best0 = jnp.min(jnp.minimum(ds + dt, inf), axis=1)
    state = (ds, dt, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
             jnp.sum(ds == 0), jnp.sum(dt == 0),
             best0, jnp.zeros((), jnp.int32))
    *_, best, _ = jax.lax.while_loop(cond, body, state)
    out = jnp.minimum(best, d_top)
    return jnp.where(out >= INF_D, INF_D, out)
