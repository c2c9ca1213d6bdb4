"""BatchHL distance-query serving driver — the paper's system end-to-end.

    PYTHONPATH=src python -m repro.launch.serve --n 2000 --batches 5

Per tick the loop ingests one batch of edge updates (mix set by
``--scenario``), maintains the labelling with BatchHL, and answers an
*open-loop* query stream: ``--queries`` arrivals per tick at Poisson rate
``--qps``, dispatched in microbatches of ``--microbatch``. Two serving
modes (DESIGN.md §5):

* **synchronous** (default): one monolithic `batchhl_update` dispatch per
  tick. Every query that arrives while it runs queues behind it on the
  device, so tail latency is bounded below by update time — the failure
  mode BatchHL exists to avoid.

* **``--pipeline``**: the update runs as *bounded chunks*
  (`core/snapshot.pipelined_update`, ``--chunk-sweeps`` relaxation waves
  per dispatch) against snapshot N+1 while query microbatches keep
  dispatching against the immutable committed snapshot N, one between
  each two chunks; the commit is an atomic version swap. A query waits
  for at most the chunk in flight and one microbatch instead of the
  whole update, answers stay exact at the version they were served
  (staleness ≤ 1 version, reported), and the final labelling is
  bit-identical to the synchronous loop's.

The loop reports p50/p95/p99 query latency and answer staleness per run;
``--verify`` checks every sampled answer against a BFS oracle *at the
version it was answered* — stale answers are exact too.

Sweep backend: ``--backend {auto,jnp,pallas}`` selects the relaxation
engine backend (DESIGN.md §3). The loop owns one `RelaxEngine`, whose
fingerprint-keyed plan cache keeps both live snapshots' tilings (the
committed one serving queries and the post-update one under repair).

Mesh sharding: ``--mesh host`` runs construction, updates, and queries
through `core/shard.py` on a `make_host_mesh` over the local devices;
``--shards M`` sets the model-axis size. Landmark counts are validated
against *both* plane groupings (data·model for maintenance, model for
queries) with an error naming the failing grouping. Backend × mesh
compose as before; in pipeline mode the maintenance chunks use the
data×model plane grouping while interleaved query microbatches regroup
over model — overlapped on the device queue instead of serialized.

Tracing: every layer boundary of the loop is a ``serve.*`` host span
(`launch/trace.py`): the tick, each host preparation step
(``serve.prepare.*``), the update dispatch or chunks, the commit, each
microbatch and each open-loop wait, and construction's phases
(``serve.construct.*``). Each tick's self seconds by span land in
`TickStats.host_s`, construction's in `ServeReport.construct_s`, and
each microbatch records its service time and the BiBFS's wave counters;
in pipeline mode each tick counts its update dispatches by phase tag
(`TickStats.update_chunks`) and each microbatch whether it ran between
two of them (`between_chunks`); either mode counts the update's fixpoint
waves (`TickStats.update_waves`) and the sweep-stream sets it built
(`stream_builds`). A finished run publishes these host records
(`trace.last_run()`).

Checkpointing: ``--ckpt-dir`` persists the *full* serve state each tick
(graph topology + labelling + version + the host edge list);
``--resume`` restarts from the newest checkpoint and continues the
exact stream (seeds are tick-indexed).

Grow-in-place: ``--capacity C`` starts the run at C edge slots instead
of provisioning the scenario's worst case; with ``--grow`` a batch that
would overflow (or that introduces vertex ids ≥ n) grows the slot
arrays and labelling planes geometrically to the next aligned size at
the version boundary — queries keep serving the committed pre-growth
snapshot throughout, and the post-growth labelling is bit-identical to
fresh construction at the grown size (DESIGN.md §6). Without ``--grow``
an overflow raises a typed ``CapacityError`` naming the tick and the
required sizes before anything is dispatched.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs import generators as gen
from repro.graphs.coo import (apply_batch, from_edges, make_batch,
                              to_numpy_wadj)
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.batch import batchhl_update
from repro.core.engine import RelaxEngine
from repro.core.query import batched_query
from repro.core.shard import (shard_batched_query, shard_batchhl_update,
                              shard_build_labelling,
                              validate_landmark_sharding)
from repro.core.growth import GrowthEvent, GrowthPolicy, ensure_capacity
from repro.core.snapshot import (Snapshot, SnapshotStore, pipelined_update,
                                 restore_extra, restore_snapshot,
                                 save_snapshot)
from repro.core import ref
from repro.checkpoint import manager as ckpt
from repro.data.scenarios import get_scenario
from repro.launch.mesh import make_host_mesh
from repro.launch import trace as tracing


@dataclasses.dataclass
class ServeConfig:
    """Everything the serving loop needs; `main()` maps CLI flags here."""
    n: int = 2000
    deg: int = 4
    #: initial graph family: "ba" (power-law, unit weights) or "road"
    #: (weighted planar grid, DESIGN.md §8). Road rounds n up to the grid
    #: size rows·cols at loop construction.
    graph: str = "ba"
    landmarks: int = 16
    batches: int = 5
    batch_size: int = 100
    scenario: str = "mixed"
    # open-loop query stream
    queries: int = 256          # arrivals per tick
    qps: float = 2000.0         # Poisson arrival rate (queries/second)
    microbatch: int = 32        # max queries per dispatched microbatch
    # serving mode
    pipeline: bool = False
    chunk_sweeps: int = 1       # relaxation waves per pipelined dispatch
    # engine / mesh
    backend: str = "auto"
    block_v: int = 512
    tile_shards: int = 1
    block_e: int | None = None   # tile-row width cap of the pallas tiling
    use_minplus_kernel: bool = False
    mesh: str = "none"
    shards: int = 1
    # autotuning (DESIGN.md §7)
    autotune: bool = False       # measure & adopt the fastest sweep impl
                                 # per snapshot shape (core/autotune.py)
    tune_table: str | None = None  # on-disk tuning table; restarts skip
                                   # the measurement entirely
    # frontier-proportional sweeps (DESIGN.md §10)
    frontier: bool = False       # relax only the tile rows the change
                                 # frontier touches (masked sweeps)
    frontier_threshold: float = 0.25  # density fallback: max fraction of
                                      # tile rows a masked wave may gather
    # capacity / grow-in-place (DESIGN.md §6)
    capacity: int | None = None  # initial edge capacity (None = provision
                                 # for the scenario's worst-case inserts)
    grow: bool = False           # grow slots/planes geometrically on
                                 # overflow instead of raising CapacityError
    growth_factor: float = 2.0
    # ops
    verify: bool = False
    ckpt_dir: str | None = None
    resume: bool = False
    seed: int = 7
    quiet: bool = False
    #: retain every committed snapshot in the report (tests/verification:
    #: lets a caller recompute any answer synchronously at its version)
    keep_history: bool = False


@dataclasses.dataclass
class MicrobatchRecord:
    """One answered microbatch: which queries, at which version."""
    tick: int
    version: int                # snapshot version the answers are exact at
    staleness: int              # versions behind the in-flight head
    qs: np.ndarray              # int32 [m] (unpadded)
    qt: np.ndarray
    answers: np.ndarray         # int32 [m]
    latencies: np.ndarray       # float64 [m] seconds, arrival → answered
    #: dispatch → answer (the `serve.microbatch` span); a query's queue
    #: wait is its latency less this
    service_s: float
    #: BiBFS waves of the microbatch (None on the mesh path)
    waves: int | None
    #: sum over the m real lanes of the waves each lane could still
    #: improve in; pad lanes are never counted (None on the mesh path)
    live_lane_waves: int | None
    #: the BiBFS ran the bit-packed unit-weight path, not Bellman-Ford
    #: waves (None on the mesh path)
    bit_packed: bool | None
    #: dispatched between two chunks of the tick's pipelined update, while
    #: it was still in flight (so answered one version behind the head)
    between_chunks: bool = False


@dataclasses.dataclass
class TickStats:
    tick: int
    version: int                # committed version after this tick
    #: from the tick's open-loop origin, taken just before the
    #: `apply_batch` dispatch and the re-tile, until the updated
    #: labelling is ready; the commit is not in it, and in pipeline mode
    #: the microbatches served between chunks are
    update_s: float
    affected: int
    label_size: int
    queries: int
    verify_mismatches: int | None = None
    grew: bool = False          # this tick grew capacity/planes (§6)
    capacity: int = 0           # edge capacity after this tick
    graph_n: int = 0            # vertex slots after this tick
    #: host self seconds by span name (`launch/trace.py`); the values
    #: sum to the tick's wall time
    host_s: dict[str, float] = dataclasses.field(default_factory=dict)
    #: pipelined update dispatches (`serve.update_chunk` spans) by the
    #: phase tag of each; empty on the sync path
    update_chunks: dict[str, int] = dataclasses.field(default_factory=dict)
    #: the update's fixpoint waves, {"search": n, "repair": n}: the
    #: `while_loop` trip counts on the sync path, the chunks times
    #: `chunk_sweeps` pipelined; empty on the mesh sync path
    update_waves: dict[str, int] = dataclasses.field(default_factory=dict)
    #: sweep-stream sets the update built, one per fixpoint phase (2;
    #: 0 under a mesh, whose waves build their own)
    stream_builds: int = 0


@dataclasses.dataclass
class ServeReport:
    """Everything a caller (benchmarks, tests) needs from one run."""
    config: ServeConfig
    ticks: list[TickStats]
    microbatches: list[MicrobatchRecord]
    final: Snapshot
    backend: str
    #: version -> committed Snapshot, populated when keep_history is set
    history: dict[int, Snapshot] = dataclasses.field(default_factory=dict)
    #: grow-in-place events, in tick order (empty without --grow)
    growth: list[GrowthEvent] = dataclasses.field(default_factory=list)
    #: construction's host self seconds by `serve.construct.*` span
    #: (empty on a resumed run)
    construct_s: dict[str, float] = dataclasses.field(default_factory=dict)

    def latencies(self) -> np.ndarray:
        if not self.microbatches:
            return np.zeros((0,))
        return np.concatenate([m.latencies for m in self.microbatches])

    def latency_percentiles(self) -> dict[str, float]:
        lat = self.latencies()
        if lat.size == 0:
            return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
        return {p: float(np.percentile(lat, q))
                for p, q in (("p50", 50), ("p95", 95), ("p99", 99))}

    def staleness(self) -> np.ndarray:
        return np.concatenate(
            [np.full(m.latencies.shape, m.staleness, np.int32)
             for m in self.microbatches]) if self.microbatches else \
            np.zeros((0,), np.int32)

    def mean_staleness(self) -> float:
        s = self.staleness()
        return float(s.mean()) if s.size else 0.0


class ServeLoop:
    """The serving pipeline: one instance owns the engine, the snapshot
    store, the scenario streams, and the open-loop query clock."""

    def __init__(self, cfg: ServeConfig):
        self.cfg = cfg
        #: optional process hooks (launch/replica.py): `on_start(snap0)`
        #: fires once the initial snapshot is in the store, before any
        #: tick; `on_commit(tick, snap)` fires after each tick's commit
        #: and checkpoint — the replica updater publishes + runs the
        #: reader ack barrier there (DESIGN.md §9).
        self.on_start = None
        self.on_commit = None
        self.scenario = get_scenario(cfg.scenario)
        if cfg.graph not in ("ba", "road"):
            raise ValueError(f"unknown graph family {cfg.graph!r}; "
                             f"choose 'ba' or 'road'")
        if cfg.graph == "road":
            # The grid generator realizes rows·cols >= n vertices; the
            # whole loop (queries, update sampling, landmarks) must agree
            # on the realized count.
            rows = max(2, int(math.isqrt(cfg.n)))
            cols = max(2, (cfg.n + rows - 1) // rows)
            cfg.n = rows * cols
        self.mesh = None
        if cfg.mesh == "host":
            self.mesh = make_host_mesh(model=cfg.shards)
            validate_landmark_sharding(self.mesh, cfg.landmarks)
        self.engine = RelaxEngine(backend=cfg.backend, block_v=cfg.block_v,
                                  shards=cfg.tile_shards,
                                  block_e=cfg.block_e,
                                  autotune=cfg.autotune,
                                  tune_table=cfg.tune_table,
                                  frontier=cfg.frontier,
                                  frontier_threshold=cfg.frontier_threshold)
        self.store: SnapshotStore | None = None
        self.report: ServeReport | None = None
        #: host spans at every layer boundary of the loop
        self.trace = tracing.SpanRecorder()
        #: (waves, live_waves [B], bit_packed) of the last `_answer`, None
        #: on the mesh
        self._counters = None
        # host-side current edge set, maintained incrementally: a
        # swap-remove list + position map keeps each tick O(batch); the
        # *order* is serve state (deletion sampling depends on it), so it
        # rides along in every checkpoint, together with the per-edge
        # weights (the serve-side mirror of the graph's w column).
        self._edge_list: list[tuple[int, int]] = []
        self._edge_pos: dict[tuple[int, int], int] = {}
        self._edge_w: dict[tuple[int, int], int] = {}
        self._oracle_adj: dict[int, dict] = {}  # version -> adjacency

    @property
    def growth_policy(self) -> GrowthPolicy:
        """Grow-in-place policy, aligned to the engine's *current* tiling
        unit (engine.plan_alignment = block_v · shards) so grown and fresh
        tilings share shape invariants, backend-independent. A property —
        not frozen at construction — because adopting an autotuned
        kernel-impl winner updates the engine's block_v, and grown vertex
        counts must respect the alignment of the tiles actually served."""
        return GrowthPolicy(factor=self.cfg.growth_factor,
                            block_v=self.engine.block_v,
                            shards=self.engine.shards)

    def _log(self, msg: str) -> None:
        if not self.cfg.quiet:
            print(msg, flush=True)

    # -- setup --------------------------------------------------------------

    def _fresh_snapshot(self) -> Snapshot:
        cfg = self.cfg
        span = self.trace.span
        with span("serve.construct.generate"):
            if cfg.graph == "road":
                edges = gen.road_grid(cfg.n, max_weight=max(
                    2, self.scenario.max_weight), seed=0)
            else:
                edges = gen.barabasi_albert(cfg.n, cfg.deg, seed=0)
        # Explicit --capacity starts the run at that size (the grow-in-place
        # entry point: pair with --grow to start small and let the stream
        # grow the slots); the default provisions the scenario's worst case
        # up front, as before.
        cap = cfg.capacity if cfg.capacity is not None else (
            edges.shape[0]
            + self.scenario.max_inserts(cfg.batches, cfg.batch_size) + 64)
        with span("serve.construct.load"):
            g = from_edges(cfg.n, edges, cap)
        with span("serve.construct.landmarks"):
            landmarks = select_landmarks_by_degree(g, cfg.landmarks)
        with span("serve.construct.tile"):
            plan = self.engine.prepare(g)
        t0 = time.time()
        with span("serve.construct.label"):
            if self.mesh is not None:
                lab = shard_build_labelling(self.mesh, g, landmarks,
                                            plan=plan)
            else:
                lab = build_labelling(g, landmarks, plan=plan)
            jax.block_until_ready(lab.dist)
        with span("serve.construct.index"):
            self._edge_list = [(int(min(a, b)), int(max(a, b)))
                               for a, b in edges[:, :2]]
            self._edge_pos = {e: i for i, e in enumerate(self._edge_list)}
            self._edge_w = {e: (int(row[2]) if edges.shape[1] > 2 else 1)
                            for e, row in zip(self._edge_list, edges)}
        self._log(f"constructed labelling: {cfg.n} vertices, "
                  f"{edges.shape[0]} edges, R={cfg.landmarks}, "
                  f"size={int(lab.label_size())}, {time.time() - t0:.2f}s "
                  f"[backend={self.engine.backend}, {self._mesh_desc()}]")
        return Snapshot(0, g, lab, plan)

    def _resumed_snapshot(self) -> Snapshot:
        cfg = self.cfg
        snap = restore_snapshot(cfg.ckpt_dir)
        # A grown run checkpoints n >= cfg.n (growth only widens), so the
        # graph's own n cannot distinguish "this config, grown" from "a
        # different, larger config". Each checkpoint therefore carries the
        # run's *base* n; resuming requires it to match exactly. Pre-growth
        # checkpoints (no base_n leaf) never grew, so their graph n is the
        # base and the old exact check applies.
        try:
            base_n = int(restore_extra(cfg.ckpt_dir,
                                       ("base_n",))["base_n"])
        except FileNotFoundError:
            base_n = snap.graph.n
        if base_n != cfg.n:
            raise ValueError(
                f"checkpoint is from a run with n={base_n} "
                f"(grown to {snap.graph.n}), config has n={cfg.n}")
        edge_arr = restore_extra(cfg.ckpt_dir, ("edge_list",))["edge_list"]
        self._edge_list = [(int(r[0]), int(r[1])) for r in edge_arr]
        self._edge_pos = {e: i for i, e in enumerate(self._edge_list)}
        self._edge_w = {e: (int(r[2]) if edge_arr.shape[1] > 2 else 1)
                        for e, r in zip(self._edge_list, edge_arr)}
        snap = dataclasses.replace(snap, plan=self.engine.prepare(snap.graph))
        self._log(f"resumed at version {snap.version}: {cfg.n} vertices, "
                  f"{len(self._edge_list)} edges, "
                  f"size={int(snap.labelling.label_size())} "
                  f"[backend={self.engine.backend}, {self._mesh_desc()}]")
        return snap

    def _mesh_desc(self) -> str:
        if self.mesh is None:
            return "unsharded"
        return (f"mesh data={self.mesh.shape['data']} "
                f"model={self.mesh.shape['model']}")

    # -- query stream -------------------------------------------------------

    def _tick_queries(self, tick: int) -> tuple[np.ndarray, np.ndarray,
                                                np.ndarray]:
        """This tick's open-loop stream: (offsets [Q] s, qs [Q], qt [Q]).

        Content and arrival offsets are pure functions of (seed, tick), so
        sync and pipelined runs — and a resumed run — see the identical
        stream; only *when* each query is answered differs.
        """
        cfg = self.cfg
        arr_rng = np.random.default_rng((cfg.seed, 101, tick))
        offsets = np.cumsum(
            arr_rng.exponential(1.0 / cfg.qps, size=cfg.queries))
        q_rng = np.random.default_rng((cfg.seed, 202, tick))
        qs, qt = self.scenario.sample_queries(q_rng, cfg.n, cfg.queries)
        return offsets, qs, qt

    def _answer(self, snap: Snapshot, qs: jax.Array,
                qt: jax.Array) -> jax.Array:
        """The microbatch's answers, ready; its BiBFS counters go to
        `self._counters` (same program, so ready with the answers)."""
        if self.mesh is None:
            d, *self._counters = batched_query(
                snap.graph, snap.labelling, qs, qt,
                use_kernel=self.cfg.use_minplus_kernel, plan=snap.plan,
                counters=True)
        else:
            d = shard_batched_query(self.mesh, snap.graph, snap.labelling,
                                    qs, qt,
                                    use_kernel=self.cfg.use_minplus_kernel,
                                    plan=snap.plan)
            self._counters = None
        jax.block_until_ready(d)
        return d

    def _drain_arrived(self, tick: int, tick_t0: float, offsets: np.ndarray,
                       qs: np.ndarray, qt: np.ndarray, served: int,
                       head_version: int, out: list[MicrobatchRecord],
                       between_chunks: bool = False) -> int:
        """Answer every query that has arrived by now, in microbatches of
        at most cfg.microbatch, against the committed snapshot. Between
        two update chunks (`between_chunks`) serve one microbatch at
        most, so the update advances a chunk per microbatch. Returns the
        new served count."""
        cfg = self.cfg
        q = offsets.shape[0]
        while served < q:
            arrived = int(np.searchsorted(offsets, time.time() - tick_t0,
                                          side="right"))
            if arrived <= served:
                break
            take = min(cfg.microbatch, arrived - served)
            idx = np.arange(served, served + take)
            # Pad to the fixed microbatch shape (one compile) by repeating
            # the first query; the pad lanes are dropped from the record.
            pad_idx = np.concatenate(
                [idx, np.full(cfg.microbatch - take, idx[0])])
            snap = self.store.committed
            with self.trace.span("serve.microbatch", tick=tick,
                                 mb=len(out)) as sp:
                d = self._answer(snap, jnp.asarray(qs[pad_idx]),
                                 jnp.asarray(qt[pad_idx]))
            t_done = time.time()
            waves = live = packed = None
            if self._counters is not None:
                waves = int(self._counters[0])
                live = int(np.asarray(self._counters[1])[:take].sum())
                packed = bool(self._counters[2])
            out.append(MicrobatchRecord(
                tick=tick, version=snap.version,
                staleness=head_version - snap.version,
                qs=qs[idx].copy(), qt=qt[idx].copy(),
                answers=np.asarray(d)[:take].copy(),
                latencies=t_done - (tick_t0 + offsets[idx]),
                service_s=sp.seconds, waves=waves, live_lane_waves=live,
                bit_packed=packed, between_chunks=between_chunks))
            served += take
            if between_chunks:
                break
        return served

    def _drain_rest(self, tick: int, tick_t0: float, offsets: np.ndarray,
                    qs: np.ndarray, qt: np.ndarray, served: int,
                    head_version: int, out: list[MicrobatchRecord]) -> int:
        """Serve the tick's remaining arrivals, sleeping the open-loop
        clock forward between stragglers."""
        q = offsets.shape[0]
        while served < q:
            wait = tick_t0 + offsets[served] - time.time()
            if wait > 0:
                with self.trace.span("serve.wait", tick=tick):
                    time.sleep(wait)
            served = self._drain_arrived(tick, tick_t0, offsets, qs, qt,
                                         served, head_version, out)
        return served

    # -- update modes -------------------------------------------------------

    def _update_sync(self, snap: Snapshot, batch, plan, g_next,
                     counts: dict) -> Snapshot:
        """The monolithic update: one dispatch, queries queue behind it.
        Its counters land in `counts`."""
        if self.mesh is None:
            g2, lab2, aff = batchhl_update(snap.graph, batch, snap.labelling,
                                           improved=True, plan=plan,
                                           g_new=g_next, stats=counts)
        else:
            g2, lab2, aff = shard_batchhl_update(self.mesh, snap.graph,
                                                 batch, snap.labelling,
                                                 improved=True, plan=plan,
                                                 g_new=g_next)
        jax.block_until_ready(lab2.dist)
        self._last_aff = aff
        return Snapshot(snap.version + 1, g2, lab2, plan)

    def _update_pipelined(self, snap: Snapshot, batch, plan, g_next,
                          tick: int, tick_t0: float, offsets, qs, qt,
                          served_box: list, out,
                          chunks: collections.Counter,
                          counts: dict) -> Snapshot:
        """The chunked update: serve one microbatch of the arrived
        queries at every yield, once the chunk before it has finished.
        Counts each dispatch by its phase tag into `chunks`; the
        update's own counters land in `counts`."""
        cfg = self.cfg
        upd = pipelined_update(snap, batch, plan=plan, g_new=g_next,
                               mesh=self.mesh, improved=True,
                               chunk_sweeps=cfg.chunk_sweeps,
                               stats=counts)
        head = snap.version + 1
        for chunk in itertools.count():
            with self.trace.span("serve.update_chunk", tick=tick,
                                 chunk=chunk) as sp:
                try:
                    tag = next(upd)
                except StopIteration as stop:
                    tag, (nxt, aff) = "finish", stop.value
                sp.set(tag=tag)
                chunks[tag] += 1
            if tag == "finish":
                break
            served_box[0] = self._drain_arrived(
                tick, tick_t0, offsets, qs, qt, served_box[0], head, out,
                between_chunks=True)
        jax.block_until_ready(nxt.labelling.dist)
        self._last_aff = aff
        return nxt

    # -- verification -------------------------------------------------------

    def _oracle(self, version: int, graph) -> dict:
        if version not in self._oracle_adj:
            self._oracle_adj[version] = to_numpy_wadj(graph)
            # A tick only ever verifies against its own two versions;
            # evict older adjacencies so --verify stays O(E) host memory
            # on long runs instead of O(ticks × E).
            for old in [v for v in self._oracle_adj if v < version - 1]:
                del self._oracle_adj[old]
        return self._oracle_adj[version]

    def _verify_tick(self, tick: int, out: list[MicrobatchRecord],
                     snapshots: dict[int, Snapshot]) -> int:
        """Check the first min(64, Q) answered queries of the tick against
        the Dijkstra oracle *at the version each was answered* — the
        staleness contract says stale answers are exact at their own
        version (for w ≡ 1 graphs the oracle degenerates to BFS)."""
        n_check = min(64, self.cfg.queries)
        wrong = checked = 0
        for m in out:
            if m.tick != tick or checked >= n_check:
                continue
            adj = self._oracle(m.version, snapshots[m.version].graph)
            for i in range(m.qs.shape[0]):
                if checked >= n_check:
                    break
                got = float(m.answers[i])
                # len(adj) is the snapshot's own n — a grown snapshot has
                # more vertices than cfg.n, and the search must see them
                # all.
                want = ref.pair_distance_w(adj, len(adj), int(m.qs[i]),
                                           int(m.qt[i]))
                want = got if (want == ref.INF and got >= 1e8) else want
                if int(m.qs[i]) == int(m.qt[i]):
                    want = 0
                wrong += int(got != want)
                checked += 1
        self._log(f"  verify: {wrong}/{n_check} mismatches")
        return wrong

    # -- the loop -----------------------------------------------------------

    def _tick(self, tick: int, out: list[MicrobatchRecord],
              growth: list[GrowthEvent],
              history: dict[int, Snapshot]) -> TickStats:
        """One tick: draw and dispatch the update batch, serve the tick's
        queries, commit, and fold the batch into the host edge set."""
        cfg = self.cfg
        span = self.trace.span
        snap = self.store.committed
        n_ins, n_del, n_rew = self.scenario.update_counts(
            tick, cfg.batch_size)
        with span("serve.prepare.snapshot_edges"):
            cur_edges = np.asarray(self._edge_list, np.int32)
        with span("serve.prepare.draw_updates"):
            ups = gen.random_batch_updates(
                cur_edges, cfg.n, n_ins=n_ins, n_del=n_del,
                seed=100 + tick, existing=self._edge_pos, n_rew=n_rew,
                max_weight=self.scenario.max_weight)
        with span("serve.prepare.make_batch"):
            batch = make_batch(ups, pad_to=cfg.batch_size)
        with span("serve.prepare.queries"):
            offsets, qs, qt = self._tick_queries(tick)
        # Insert ops alone move topology slots; deletions flip
        # validity in place and reweights touch only the w column,
        # so a reweight-only tick reuses the committed tiling.
        has_ins = any(not int(up[2]) for up in ups)

        # Grow-in-place check *before* any dispatch (DESIGN.md §6): an
        # overflowing batch grows the working snapshot — same version,
        # larger slots/planes — or raises a typed CapacityError naming
        # this tick. The committed snapshot keeps serving queries
        # untouched either way; the grown shapes first become visible
        # to readers at the next commit's pointer swap.
        with span("serve.prepare.capacity"):
            work, event = ensure_capacity(snap, batch, self.growth_policy,
                                          grow=cfg.grow, tick=tick)
        if event is not None:
            growth.append(event)
            self._log(f"  grow: capacity {event.old_capacity}->"
                      f"{event.new_capacity}, n {event.old_n}->"
                      f"{event.new_n} (needed {event.required_capacity}"
                      f"/{event.required_n})")

        served_box = [0]
        tick_t0 = time.time()
        # One tiling per tick, prepared from the post-update snapshot
        # (the engine contract); the keyed plan cache keeps the
        # committed snapshot's tiling alive alongside it. Growth moved
        # topology slots (capacity/n changed → new fingerprint), so it
        # forces a clean retile exactly like an insertion does.
        with span("serve.apply_batch", tick=tick):
            g_next = apply_batch(work.graph, batch)
        retiles = self.engine.retile_count
        with span("serve.prepare.retile", tick=tick) as sp:
            plan = self.engine.prepare(
                g_next, topology_changed=has_ins or event is not None)
            sp.set(retiled=self.engine.retile_count > retiles)
        chunks = collections.Counter()
        counts: dict = {}
        if cfg.pipeline:
            nxt = self._update_pipelined(work, batch, plan, g_next,
                                         tick, tick_t0, offsets, qs, qt,
                                         served_box, out, chunks, counts)
        else:
            with span("serve.update", tick=tick):
                nxt = self._update_sync(work, batch, plan, g_next, counts)
        t_upd = time.time() - tick_t0
        with span("serve.commit", tick=tick):
            self.store.commit(nxt)
        if cfg.keep_history:
            history[nxt.version] = nxt
        served_box[0] = self._drain_rest(
            tick, tick_t0, offsets, qs, qt, served_box[0],
            nxt.version, out)

        # Fold the tick's updates into the incremental edge set
        # (op 0 = insert, 1 = delete, 2 = reweight).
        with span("serve.prepare.fold"):
            for up in ups:
                u, v, op = up[0], up[1], int(up[2])
                w = int(up[3]) if len(up) > 3 else 1
                k = (min(u, v), max(u, v))
                if op == 1:
                    i = self._edge_pos.pop(k, None)
                    if i is not None:
                        self._edge_w.pop(k, None)
                        last = self._edge_list.pop()
                        if i < len(self._edge_list):
                            self._edge_list[i] = last
                            self._edge_pos[last] = i
                elif op == 2:
                    if k in self._edge_pos:
                        self._edge_w[k] = w
                elif k not in self._edge_pos:
                    self._edge_pos[k] = len(self._edge_list)
                    self._edge_list.append(k)
                    self._edge_w[k] = w

        tick_mbs = [m for m in out if m.tick == tick]
        lat = (np.concatenate([m.latencies for m in tick_mbs])
               if tick_mbs else np.zeros((1,)))
        stale = sum(int(m.staleness > 0) * m.qs.shape[0]
                    for m in tick_mbs)
        waves = counts.get("update_waves", {})
        with span("serve.prepare.stats"):
            stats = TickStats(
                tick=tick, version=nxt.version, update_s=t_upd,
                affected=int(jnp.sum(self._last_aff)),
                label_size=int(nxt.labelling.label_size()),
                queries=int(served_box[0]),
                grew=event is not None,
                capacity=nxt.graph.capacity, graph_n=nxt.graph.n,
                update_chunks=dict(chunks),
                update_waves={k: int(waves[k]) for k in ("search", "repair")
                              if k in waves},
                stream_builds=int(counts.get("stream_builds", 0)))
        self._log(
            f"tick {tick}: update {t_upd * 1e3:.1f}ms "
            f"({stats.affected} affected, v{nxt.version}) | "
            f"{stats.queries} queries p50 "
            f"{np.percentile(lat, 50) * 1e3:.1f}ms p99 "
            f"{np.percentile(lat, 99) * 1e3:.1f}ms "
            f"({stale} stale) | label size {stats.label_size} | "
            f"host prep {self.trace.seconds('serve.prepare.') * 1e3:.1f}ms"
            f" | BiBFS waves {[m.waves for m in tick_mbs]}"
            f" ({sum(bool(m.bit_packed) for m in tick_mbs)}/{len(tick_mbs)}"
            f" bit-packed) | update waves {stats.update_waves}, "
            f"{stats.stream_builds} stream builds"
            + (f" | {sum(chunks.values())} update chunks {dict(chunks)}, "
               f"{sum(m.between_chunks for m in tick_mbs)} microbatches "
               f"between them" if chunks else ""))

        if cfg.verify:
            snapshots = {snap.version: snap, nxt.version: nxt}
            stats.verify_mismatches = self._verify_tick(
                tick, tick_mbs, snapshots)

        if cfg.ckpt_dir:
            edge_rows = np.asarray(
                [(u, v, self._edge_w.get((u, v), 1))
                 for u, v in self._edge_list],
                np.int32).reshape(-1, 3)
            save_snapshot(
                cfg.ckpt_dir, nxt,
                extra={"edge_list": edge_rows,
                       "base_n": np.int64(cfg.n)})
        if self.on_commit is not None:
            self.on_commit(tick, nxt)
        return stats

    def run(self) -> ServeReport:
        cfg = self.cfg
        resumable = (cfg.resume and cfg.ckpt_dir
                     and ckpt.latest_step(cfg.ckpt_dir) is not None)
        snap0 = self._resumed_snapshot() if resumable \
            else self._fresh_snapshot()
        construct_s = self.trace.take()
        self.store = SnapshotStore(snap0)
        if self.on_start is not None:
            self.on_start(snap0)
        ticks: list[TickStats] = []
        out: list[MicrobatchRecord] = []
        growth: list[GrowthEvent] = []
        history: dict[int, Snapshot] = {}
        if cfg.keep_history:
            history[snap0.version] = snap0
        self._last_aff = None

        span = self.trace.span
        for tick in range(snap0.version, cfg.batches):
            with span("serve.tick", tick=tick):
                stats = self._tick(tick, out, growth, history)
            stats.host_s = self.trace.take()
            ticks.append(stats)

        self.report = ServeReport(config=cfg, ticks=ticks, microbatches=out,
                                  final=self.store.committed,
                                  backend=self.engine.backend,
                                  history=history, growth=growth,
                                  construct_s=construct_s)
        tracing.publish(tracing.RunRecord(
            host_s=tuple(t.host_s for t in ticks),
            microbatches=tuple(tracing.MicrobatchHost(
                len(m.qs), m.service_s, m.waves, m.live_lane_waves,
                m.bit_packed, m.between_chunks)
                for m in out),
            construct_s=construct_s,
            update_chunks=tuple(t.update_chunks for t in ticks),
            update_waves=tuple(t.update_waves for t in ticks),
            stream_builds=tuple(t.stream_builds for t in ticks)))
        pct = self.report.latency_percentiles()
        mode = "pipeline" if cfg.pipeline else "sync"
        engine = self.engine
        engine_desc = (
            "" if engine.backend == "jnp" else
            f"retiles={engine.retile_count}/{cfg.batches + 1} prepares, "
            f"{engine.plan_cache_hits} plan-cache hits, "
            f"{engine.stale_cache_retiles} stale-cache catches, "
            f"tile-shards={engine.shards}, ")
        self._log(
            f"latency: p50 {pct['p50'] * 1e3:.1f}ms "
            f"p95 {pct['p95'] * 1e3:.1f}ms p99 {pct['p99'] * 1e3:.1f}ms | "
            f"staleness mean {self.report.mean_staleness():.2f} versions "
            f"behind head [{mode}, chunk-sweeps={cfg.chunk_sweeps}, "
            f"scenario={cfg.scenario}]")
        if growth:
            final_g = self.store.committed.graph
            self._log(f"grew {len(growth)}x: capacity "
                      f"{growth[0].old_capacity}->{final_g.capacity}, "
                      f"n {growth[0].old_n}->{final_g.n} "
                      f"[factor={cfg.growth_factor:g}, "
                      f"v-align={engine.plan_alignment}]")
        self._log(f"serve loop done [backend={engine.backend}, "
                  f"{engine_desc}{self._mesh_desc()}, mode={mode}]")
        return self.report


def main() -> None:
    # The parser is generated from the composable spec dataclasses
    # (launch/config.py) — one source of truth shared with the replica
    # roles; `--config <spec.json>` launches from a serialized ServeSpec
    # and flat flags remain as the (warned) legacy override surface.
    from repro.launch import config as cfgmod
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = cfgmod.build_parser(__doc__.splitlines()[0])
    args = ap.parse_args()
    spec = cfgmod.spec_from_cli(args, ap)
    autotune = spec.engine.autotune or spec.engine.tune_table is not None
    cfg = spec.to_serve_config(autotune=autotune)
    try:
        # Config validation (mesh shape, landmark groupings, scenario,
        # backend) happens at construction; runtime errors inside run()
        # propagate with their tracebacks rather than masquerading as
        # CLI misuse.
        loop = ServeLoop(cfg)
    except ValueError as e:
        ap.error(str(e))
    report = loop.run()
    if cfg.verify:
        bad = sum(t.verify_mismatches or 0 for t in report.ticks)
        if bad:
            raise SystemExit(f"verify FAILED: {bad} mismatched answers")


if __name__ == "__main__":
    main()
