"""Distance queries: Eq.-3 highway upper bound + bounded BiBFS on G[V\\R].

Queries are processed in batches (the serving reality at scale). The upper
bound over a batch is a min-plus (tropical) product
    d⊤[q] = min_{i,j}  L[i, s_q] + H[i, j] + L[j, t_q]
dispatched by `use_kernel`: the Pallas `minplus` kernel when True, a pure
jnp contraction when False (the default everywhere off-TPU). The bounded
bidirectional BFS runs all queries in lockstep as masked frontier waves
with a global early-exit; each wave is an edge-relaxation sweep routed
through the relaxation engine (`core/engine.py`), so passing a `RelaxPlan`
runs the tiled Pallas `edge_relax` kernel while the default `plan=None`
runs the jnp segment-min reference — see DESIGN.md §3. On a graph whose
valid edges all weigh 1 the waves are BFS level steps over one packed
bit plane per side instead (the `frontier_or` sweep; DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.graphs.coo import Graph, INF_D
from repro.core.engine import RelaxPlan, frontier_or_sweep, relax_sweep
from repro.core.labelling import HighwayLabelling, landmark_onehot


def effective_label_planes(dist: jax.Array, hub: jax.Array, own: jax.Array,
                           landmarks_full: jax.Array) -> jax.Array:
    """[P, V] effective label values for a plane slice (dist/hub [P, V]).

    `own` [P] is each plane's landmark id, `landmarks_full` [R] the complete
    landmark set. Entirely per-plane, so `core/shard.py` evaluates it on
    shard-local planes; `effective_labels` below is the full-plane wrapper.
    """
    v_ids = jnp.arange(dist.shape[1])
    is_landmark_v = jnp.any(v_ids[None, :] == landmarks_full[:, None], axis=0)
    mask = (dist < INF_D) & ~hub & ~is_landmark_v[None, :]
    vals = jnp.where(mask, dist, INF_D)
    # Landmark columns get the trivial (own, 0) one-hot entry.
    onehot = jnp.where(own[:, None] == landmarks_full[None, :],
                       0, INF_D).astype(jnp.int32)
    cols = landmarks_full
    return vals.at[:, cols].set(jnp.minimum(vals[:, cols], onehot))


def effective_labels(labelling: HighwayLabelling) -> jax.Array:
    """[R, V] label values with landmark columns replaced by highway one-hots.

    For a landmark vertex v = r_k the minimal labelling stores nothing; its
    Eq.-3 role is played by the trivial entry (r_k, 0), which composes with
    the highway to give exact landmark distances (Def. 3.3).
    """
    return effective_label_planes(labelling.dist, labelling.hub,
                                  labelling.landmarks, labelling.landmarks)


def _minplus_bound(s_lab: jax.Array, highway: jax.Array,
                   t_lab: jax.Array) -> jax.Array:
    """[B,R] ⊗ [R,R] ⊗ [B,R] tropical contraction → [B]."""
    # mid[b, j] = min_i s_lab[b, i] + H[i, j]
    mid = jnp.min(s_lab[:, :, None] + highway[None, :, :], axis=1)
    return jnp.min(mid + t_lab, axis=1)


def query_upper_bound(labelling: HighwayLabelling, s: jax.Array,
                      t: jax.Array, use_kernel: bool = False) -> jax.Array:
    """d⊤ for query pairs (s[q], t[q]) — Eq. 3.

    use_kernel=False (the default) runs the jnp tropical contraction;
    use_kernel=True dispatches to the Pallas `minplus` kernel (compiled on
    TPU, interpret-mode elsewhere).
    """
    lab = effective_labels(labelling)
    s_lab = lab[:, s].T  # [B, R]
    t_lab = lab[:, t].T
    s_lab = jnp.minimum(s_lab, INF_D)
    t_lab = jnp.minimum(t_lab, INF_D)
    if use_kernel:
        from repro.kernels.minplus import ops as minplus_ops
        return minplus_ops.minplus_bound(s_lab, labelling.highway, t_lab)
    return jnp.minimum(_minplus_bound(s_lab, labelling.highway, t_lab), INF_D)


@partial(jax.jit, static_argnames=("max_steps",))
def bounded_bibfs(g: Graph, landmarks: jax.Array, s: jax.Array, t: jax.Array,
                  bound: jax.Array, max_steps: int = 64,
                  plan: RelaxPlan | None = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Distance-bounded bidirectional search on G[V\\R], batched over
    queries.

    Returns `(d, waves, live_waves, bit_packed)`. `d` [B] is
    d_{G[V\\R]}(s,t) clamped at `bound` (if the sparsified distance is
    >= bound the return is >= bound, which is all the caller needs).
    `waves` is the loop's trip count; `live_waves` [B] counts, per query,
    the waves at whose start that query could still improve. All queries
    run until the slowest is done, so sum(live_waves) / (waves · B) is
    the share of lane-waves that did useful work. `bit_packed` says
    which of the two expansions below ran.

    Expansion is a Bellman-Ford wave — an engine-dispatched relaxation
    sweep over each side's whole distance plane, vmapped over the query
    batch (`plan` selects the backend, None = jnp). After k waves a side
    is exact on every shortest path of ≤ k edges, so once both sides have
    run ls/lt waves any path still unaccounted for has ≥ ls+lt+1 edges
    and therefore weight ≥ (ls+lt+1)·wmin — the weighted termination
    bound.

    When every valid edge has weight 1 (a property of the input, checked
    on device; `lax.cond` picks the branch) a wave is a BFS level step
    instead: a vertex changes only when first reached, and then to the
    side's wave count. All B queries of a side then share one packed
    word plane — bit q of word q // 32 is "reached by query q" — and a
    wave is one OR sweep of the frontier words (`frontier_or_sweep`),
    masked by the visited words and the landmarks, which then writes the
    new level into the [B, V] distance plane. The planes, the meet, the
    side choice and the termination test are the Bellman-Ford path's, so
    `d`, `waves` and `live_waves` are bit-identical to it.
    """
    n = g.n
    b = s.shape[0]
    blocked = landmark_onehot(landmarks, n)                   # bool[V]

    inf = INF_D
    dist_s = jnp.full((b, n), inf, jnp.int32).at[jnp.arange(b), s].set(0)
    dist_t = jnp.full((b, n), inf, jnp.int32).at[jnp.arange(b), t].set(0)
    # A landmark endpoint never expands (searches run on G[V\R]).
    s_ok = ~blocked[s]
    t_ok = ~blocked[t]
    dist_s = jnp.where(s_ok[:, None], dist_s, inf)
    dist_t = jnp.where(t_ok[:, None], dist_t, inf)

    # Smallest live edge weight, for the termination bound. Clipped: ≥ 1
    # so the bound still advances on w ≡ 1 graphs, and ≤ 2^20 so the
    # product (ls+lt+1)·wmin — at most (max_steps+1)·wmin — stays far from
    # int32 wrap even on near-INF_D weights (an edgeless graph min()s to
    # INF_D before the clip).
    wmin = jnp.clip(jnp.min(jnp.where(g.valid, g.w, INF_D), initial=INF_D),
                    1, 1 << 20)
    unit = jnp.all(jnp.where(g.valid, g.w, 1) == 1)

    def best_meet(ds, dt):
        return jnp.min(jnp.minimum(ds + dt, inf), axis=1)     # [B]

    def can_improve(ls, lt, best):
        return (ls + lt + 1) * wmin < jnp.minimum(best, bound)     # [B]

    def search(expand, planes_s, planes_t):
        """The lockstep loop over both sides. A side's state is a tuple
        whose first entry is its [B, V] distance plane; `expand(side,
        level)` returns the side after one wave, and the count of plane
        entries that wave changed."""
        def cond(state):
            xs, xt, ls, lt, fs, ft, best, step, live = state
            return jnp.any(can_improve(ls, lt, best)) & (step < max_steps)

        def body(state):
            xs, xt, ls, lt, fs, ft, best, step, live = state
            live = live + can_improve(ls, lt, best).astype(jnp.int32)
            # Expand the side whose last wave changed fewer entries (the
            # paper's smaller-frontier BiBFS optimization; on w ≡ 1
            # graphs the changed count IS the new frontier size).
            # lax.cond executes only the chosen side's sweep — the
            # edge-array read per wave is the memory floor here.
            def s_side(args):
                xs, xt, ls, lt, fs, ft = args
                xs, fs = expand(xs, ls + 1)
                return xs, xt, ls + 1, lt, fs, ft

            def t_side(args):
                xs, xt, ls, lt, fs, ft = args
                xt, ft = expand(xt, lt + 1)
                return xs, xt, ls, lt + 1, fs, ft

            xs, xt, ls, lt, fs, ft = jax.lax.cond(
                fs <= ft, s_side, t_side, (xs, xt, ls, lt, fs, ft))
            best = jnp.minimum(best, best_meet(xs[0], xt[0]))
            return xs, xt, ls, lt, fs, ft, best, step + 1, live

        zero = jnp.zeros((), jnp.int32)
        state = (planes_s, planes_t, zero, zero,
                 jnp.sum(dist_s == 0), jnp.sum(dist_t == 0),
                 best_meet(dist_s, dist_t), zero,
                 jnp.zeros((b,), jnp.int32))
        *_, best, waves, live = jax.lax.while_loop(cond, body, state)
        return best, waves, live

    def bellman_ford():
        def expand(side, level):
            """One Bellman-Ford wave: relax every live edge from the
            current plane — the same sweep primitive (and the same
            kernel) as the update-side searches. Landmark vertices never
            acquire a distance (the search runs on G[V\\R])."""
            (dist_x,) = side
            cand = jax.vmap(
                lambda k: relax_sweep(plan, g, k, 1, inf))(dist_x)
            cand = jnp.where(blocked[None, :], inf, cand)
            nd = jnp.minimum(dist_x, cand)
            return (nd,), jnp.sum(nd != dist_x)

        return search(expand, (dist_s,), (dist_t,))

    def bit_packed():
        lane = jnp.arange(b)
        word, bit = lane // 32, (lane % 32).astype(jnp.uint32)
        sweep = frontier_or_sweep(plan, g, b)

        def seed(x, ok):
            # Lanes own distinct bits, so adding them ORs them.
            return jnp.zeros((-(-b // 32), n), jnp.uint32).at[word, x].add(
                jnp.where(ok, jnp.uint32(1) << bit, 0))

        def expand(side, level):
            """One BFS level: the unvisited, non-landmark vertices with a
            frontier in-neighbour, per lane, at distance `level`."""
            dist_x, front, seen = side
            new = sweep(front) & ~seen
            hit = (new[word] >> bit[:, None]) & 1 != 0           # [B, V]
            changed = jnp.sum(jax.lax.population_count(new).astype(
                jnp.int32))
            return (jnp.where(hit, level, dist_x), new, seen | new), changed

        # Landmarks count as visited in every lane, so never reached.
        walls = jnp.where(blocked, ~jnp.uint32(0), jnp.uint32(0))
        front_s, front_t = seed(s, s_ok), seed(t, t_ok)
        return search(expand, (dist_s, front_s, front_s | walls),
                      (dist_t, front_t, front_t | walls))

    best, waves, live = jax.lax.cond(unit, bit_packed, bellman_ford)
    return best, waves, live, unit


def batched_query(g: Graph, labelling: HighwayLabelling, s: jax.Array,
                  t: jax.Array, max_steps: int = 64,
                  use_kernel: bool = False,
                  plan: RelaxPlan | None = None, counters: bool = False):
    """Exact distances Q(s,t) = min(d_{G[V\\R]}(s,t), d⊤) — paper §4.

    `use_kernel` dispatches the upper bound to the minplus kernel; `plan`
    dispatches the BiBFS sweeps to the edge_relax kernel (both default to
    the jnp reference paths). With `counters` the return is
    `(d, waves, live_waves, bit_packed)`, the BiBFS's counters beside the
    answers (see `bounded_bibfs`).
    """
    d_top = query_upper_bound(labelling, s, t, use_kernel=use_kernel)
    d_sparse, *counts = bounded_bibfs(g, labelling.landmarks, s, t, d_top,
                                      max_steps, plan)
    out = jnp.minimum(d_sparse, d_top)
    d = jnp.where(out >= INF_D, INF_D, out)
    return (d, *counts) if counters else d
