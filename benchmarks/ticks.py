"""Serving-tick latency trajectory: backend × mesh, the CI bench preset.

The scale story of this repo lives or dies on two numbers per tick — the
batch-update latency and the query-batch latency — across the four
backend × mesh configurations that PRs 1–3 built:

    ticks/<dataset>/<backend>/<mesh>/construct   (one-off, seconds→us)
    ticks/<dataset>/<backend>/<mesh>/update      (median per-tick)
    ticks/<dataset>/<backend>/<mesh>/query       (median per-tick)

PR 4 adds the *serving-pipeline* trajectory: the open-loop query stream
of `launch/serve.py` measured under concurrent update load, synchronous
vs pipelined (DESIGN.md §5):

    serve/<dataset>/<backend>/<mode>/q_p50|q_p95|q_p99   (per-query s→us)
    serve/<dataset>/<backend>/<mode>/update              (min steady tick)
    serve/<dataset>/<backend>/<mode>/staleness           (mean versions
                                                          behind head —
                                                          telemetry, not
                                                          a latency)

where mode ∈ {sync, pipeline}. The pipeline's whole point shows up here:
sync q_p99 tracks the update latency (queries queue behind the monolithic
dispatch), pipeline q_p99 tracks one chunk + one microbatch.

PR 5 adds mode `growth` — the pure-insertion `growth` scenario run
pipelined with grow-in-place enabled (`--capacity` below the stream's
final size, DESIGN.md §6), its capacity sized so the geometric growth
lands on a steady-state tick: the q percentiles price serving *through*
the growth retrace/retile, and the row's `derived` field records the
growth count and capacity trajectory.

PR 6 adds the autotuner trajectory (DESIGN.md §7): the pallas tick and
serve rows run with ``autotune=True`` (the engine measures its candidate
configs once per snapshot shape and serves the winner — the winning impl
is recorded in each row's ``derived``), and three new row families pin
the jnp-vs-tuned comparison directly:

    tune/<dataset>/jnp      reference sweep, steady min-of-k
    tune/<dataset>/pallas   tuned winner, same wave, same stat
    tune/crossover          telemetry: smallest benched vertex count
                            where the tuned config won (unit=vertices)

PR 7 adds the weighted-metric trajectory (DESIGN.md §8): tick rows on
the weighted road grid (``ticks/road_2k/<backend>/none``) and the
``traffic`` serving rows (``serve/road_2k/<backend>/traffic``) — weight
churn dominates each batch, every 4th tick is weight-change-only, and
the Dijkstra-exact answers ride the same percentile contract.

PR 8 adds the replica-tier saturation trajectory (DESIGN.md §9): a real
multi-process topology — one updater publishing versions, R mmap'd
reader replicas behind the coalescing router of ``launch/replica.py`` —
rammed with an open-loop client stream at a rising qps ladder until the
p99 breaks the SLO:

    serve/<dataset>/<backend>/max_qps_r1    sustained qps, 1 reader
    serve/<dataset>/<backend>/max_qps_r2    sustained qps, 2 readers

(``unit=qps;better=higher`` — compare.py gates these with the inverted
ratio; r2/r1 is the throughput the second reader buys.)

PR 10 adds the frontier-proportional trajectory (DESIGN.md §10):

    ticks/<dataset>/<backend>/footprint_small   quiet-tick trickle,
                                                no-retile op mix
    ticks/<dataset>/<backend>/footprint_large   full mixed batch

both timed with the frontier mode on (each row's ``derived`` records
the same tick stream's full-sweep latency as ``fullsweep_us``) — the
scale-with-batch-footprint claim in two gated rows, on both the
hub-dominated BA graph and the planar road grid where change stays
local.

Rows follow the ``name,us_per_call,derived`` contract of benchmarks/run.py;
``python -m benchmarks.run --preset quick --json BENCH_pr5.json`` persists
them in the bench-trajectory JSON format that `benchmarks/compare.py`
gates against the committed `benchmarks/baseline.json` (>25% regressions
on any gated tick latency *or* serve percentile fail the CI `bench` job).

The quick preset is sized for shared CI runners: one small dataset, a few
ticks, the degenerate host mesh on however many devices the runner
exposes. The point is the *trajectory* (same shapes every PR), not
absolute hardware truth.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BA_PARAMS, DATASETS, ROAD_PARAMS, emit
from repro.graphs import generators as gen
from repro.graphs.coo import apply_batch, from_edges, make_batch
from repro.core.batch import batchhl_update
from repro.core.construct import build_labelling, select_landmarks_by_degree
from repro.core.engine import RelaxEngine
from repro.core.query import batched_query
from repro.core.shard import (shard_batched_query, shard_batchhl_update,
                              shard_build_labelling)
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import ServeConfig, ServeLoop

#: datasets the serve loop can regenerate itself (it builds its own BA
#: graph from `common.BA_PARAMS` — one source of truth with DATASETS).
SERVE_DATASETS = {"ba_2k"}


def _tick_loop(name: str, g0, landmarks, edges, backend: str, mesh,
               ticks: int, batch_size: int, queries: int,
               block_v: int, tile_shards: int,
               autotune: bool = False) -> list[str]:
    n = g0.n
    engine = RelaxEngine(backend=backend, block_v=block_v,
                         shards=tile_shards, autotune=autotune)
    plan = engine.prepare(g0)

    t0 = time.time()
    if mesh is None:
        lab = build_labelling(g0, landmarks, plan=plan)
    else:
        lab = shard_build_labelling(mesh, g0, landmarks, plan=plan)
    jax.block_until_ready(lab.dist)
    rows = [emit(f"{name}/construct", time.time() - t0, f"R={len(landmarks)}")]

    rng = np.random.default_rng(11)
    # Weighted datasets carry an [E, 3] edge array; the host-side fold
    # only tracks membership (weights live in the device graph).
    g, cur_edges = g0, (edges[:, :2] if edges.shape[1] > 2 else edges)
    t_upd, t_q = [], []
    for tick in range(ticks):
        ups = gen.random_batch_updates(cur_edges, n, n_ins=batch_size // 2,
                                       n_del=batch_size // 2,
                                       seed=500 + tick)
        batch = make_batch(ups, pad_to=batch_size)
        has_ins = any(not d for (_, _, d) in ups)
        t0 = time.time()
        g_next = apply_batch(g, batch)
        plan = engine.prepare(g_next, topology_changed=has_ins)
        if mesh is None:
            g, lab, aff = batchhl_update(g, batch, lab, improved=True,
                                         plan=plan, g_new=g_next)
        else:
            g, lab, aff = shard_batchhl_update(mesh, g, batch, lab,
                                               improved=True, plan=plan,
                                               g_new=g_next)
        jax.block_until_ready(lab.dist)
        t_upd.append(time.time() - t0)

        qs = jnp.asarray(rng.integers(0, n, queries), jnp.int32)
        qt = jnp.asarray(rng.integers(0, n, queries), jnp.int32)
        t0 = time.time()
        if mesh is None:
            d = batched_query(g, lab, qs, qt, plan=plan)
        else:
            d = shard_batched_query(mesh, g, lab, qs, qt, plan=plan)
        jax.block_until_ready(d)
        t_q.append(time.time() - t0)

        # Fold this tick's updates into the edge set for the next one.
        es = {(int(min(u, v)), int(max(u, v))) for u, v in cur_edges}
        for u, v, is_del in ups:
            k = (min(u, v), max(u, v))
            es.discard(k) if is_del else es.add(k)
        cur_edges = np.asarray(sorted(es), np.int32)

    # Min of the steady-state ticks: tick 0 pays compilation and tick 1
    # can pay a second trace (the labelling comes back mesh-sharded after
    # the first update), so both are warmup; min (not median) because a
    # transient load burst on a shared runner inflates several consecutive
    # ticks at once, and the fastest tick is the best estimate of the
    # unloaded latency the gate should track.
    warm = 2 if ticks > 2 else 1 if ticks > 1 else 0
    steady_upd = t_upd[warm:]
    steady_q = t_q[warm:]
    impl = plan.impl if plan is not None and plan.backend == "pallas" \
        else backend
    rows.append(emit(f"{name}/update", float(np.min(steady_upd)),
                     f"stat=min;ticks={ticks};batch={batch_size};"
                     f"impl={impl}"))
    rows.append(emit(f"{name}/query", float(np.min(steady_q)),
                     f"stat=min;ticks={ticks};B={queries};impl={impl}"))
    return rows


def _footprint_rows(ds: str, g0, landmarks, edges, backend: str,
                    ticks: int, block_v: int, tile_shards: int,
                    large: int = 64) -> list[str]:
    """PR 10: the frontier-proportional trajectory (DESIGN.md §10).

    ``ticks/<ds>/<backend>/footprint_small|footprint_large`` time the
    steady-state update tick with the frontier mode on at two batch
    footprints:

    ``footprint_small`` is the quiet-tick trickle — the batch size the
    `bursty` scenario uses between bursts (``max(2, round(0.1*batch))``),
    carrying the no-retile op mix of the production trickle: re-weights
    on weighted datasets (the `traffic` shape), deletions on unweighted
    ones (expiry churn). ``topology_changed=False`` end to end, so the
    tick prices plan+frontier reuse, not retiling.

    ``footprint_large`` is the preset's full mixed batch over the whole
    vertex range — the same shape as the main tick rows, with the
    frontier on. At that footprint the density fallback fires and the
    row tracks the bookkeeping overhead of carrying the bitmaps.

    The pair is the scale-with-footprint claim in two numbers. Each
    row's ``derived`` also records the full-sweep latency of the *same*
    tick stream (``fullsweep_us=``), so the masked win — or, on
    hub-dominated graphs where one block-hop saturates the bitmap, the
    masked *overhead* — is auditable per row rather than only against
    the committed baseline trajectory.
    """
    n = g0.n
    weighted = edges.shape[1] > 2
    small = max(2, round(large * 0.1))
    rows = []
    for frontier in (True, False):
        engine = RelaxEngine(backend=backend, block_v=block_v,
                             shards=tile_shards, frontier=frontier,
                             autotune=(backend == "pallas"))
        lab0 = build_labelling(g0, landmarks, plan=engine.prepare(g0))
        jax.block_until_ready(lab0.dist)
        for tag, bs, trickle in (("footprint_small", small, True),
                                 ("footprint_large", large, False)):
            g, lab = g0, lab0
            cur = edges[:, :2] if weighted else edges
            t_upd = []
            for tick in range(ticks):
                # Same deterministic stream for both engines (seed only).
                if trickle and weighted:
                    ups = gen.random_batch_updates(cur, n, n_ins=0,
                                                   n_del=0, n_rew=bs,
                                                   max_weight=8,
                                                   seed=900 + tick)
                elif trickle:
                    ups = gen.random_batch_updates(cur, n, n_ins=0,
                                                   n_del=bs,
                                                   seed=900 + tick)
                else:
                    ups = gen.random_batch_updates(cur, n, n_ins=bs // 2,
                                                   n_del=bs // 2,
                                                   seed=900 + tick)
                batch = make_batch(ups, pad_to=bs)
                # Trickle ops never consume or free slot pairs in a way
                # the tiling sees; only insertions force a retile.
                has_ins = (not trickle) and any(not u[2] for u in ups)
                t0 = time.time()
                g_next = apply_batch(g, batch)
                plan = engine.prepare(g_next, topology_changed=has_ins)
                g, lab, _ = batchhl_update(g, batch, lab, improved=True,
                                           plan=plan, g_new=g_next)
                jax.block_until_ready(lab.dist)
                t_upd.append(time.time() - t0)
                if not (trickle and weighted):
                    # Fold membership churn (re-weights don't change it).
                    es = {(int(min(u, v)), int(max(u, v))) for u, v in cur}
                    for u, v, is_del, *_ in ups:
                        k = (min(u, v), max(u, v))
                        es.discard(k) if is_del else es.add(k)
                    cur = np.asarray(sorted(es), np.int32)
            warm = 2 if ticks > 2 else 1 if ticks > 1 else 0
            rows.append((tag, bs, trickle, frontier,
                         float(np.min(t_upd[warm:]))))
    by_tag = {}
    for tag, bs, trickle, frontier, m in rows:
        by_tag.setdefault(tag, {})[frontier] = (bs, trickle, m)
    out = []
    for tag, d in by_tag.items():
        bs, trickle, masked_s = d[True]
        _, _, full_s = d[False]
        ops = ("rew" if weighted else "del") if trickle else "mixed"
        out.append(emit(
            f"ticks/{ds}/{backend}/{tag}", masked_s,
            f"stat=min;ticks={ticks};batch={bs};ops={ops};frontier=on;"
            f"fullsweep_us={full_s * 1e6:.1f}"))
    return out


def _tune_rows(ds: str, g, tile_shards: int,
               block_v: int) -> tuple[list[str], float]:
    """The `tune/` rows: one autotuner measurement per dataset shape.

    `tune/<ds>/jnp` is the reference wave's steady latency and
    `tune/<ds>/pallas` the tuned winner's (both min-of-k after warmup —
    `autotune.measure_compiled`), so the pair *is* the jnp-vs-tuned
    comparison the PR-6 acceptance reads. The crossover — smallest
    benched vertex count where the tuned config wins — is recorded in
    the `derived` field of `tune/crossover` (its value is the vertex
    count, unit=vertices: telemetry like the staleness rows, sub-min-us
    by construction so the compare gate never flakes on it moving).
    """
    from repro.core import autotune as at

    res = at.tune(g, shards=tile_shards, block_v=block_v, iters=5)
    cfg = res.config
    speed = res.jnp_us / res.steady_us if res.steady_us else float("inf")
    info = f"R=8;cap={g.src.shape[0]};stat=min"
    rows = [emit(f"tune/{ds}/jnp", res.jnp_us / 1e6, info),
            emit(f"tune/{ds}/pallas", res.steady_us / 1e6,
                 f"impl={cfg.impl};block_v={cfg.block_v};"
                 f"block_e={cfg.block_e};tile_shards={cfg.tile_shards};"
                 f"compile_us={res.compile_us:.1f};speedup={speed:.2f}x;"
                 f"stat=min")]
    return rows, speed


def _serve_loop(name: str, n: int, deg: int, backend: str, mode: str,
                ticks: int, batch_size: int, queries: int, landmarks: int,
                block_v: int, tile_shards: int, qps: float,
                microbatch: int, capacity: int | None = None,
                autotune: bool = False,
                scenario: str | None = None,
                graph: str = "ba") -> list[str]:
    """One ServeLoop run → the serve/ percentile + staleness rows.

    Percentiles are computed over the steady-state ticks only (the same
    warmup convention as `_tick_loop`: tick 0 pays compilation, tick 1
    can pay a reshard retrace), per query, arrival → answered.

    mode "growth" runs the pure-insertion `growth` scenario pipelined
    with grow-in-place enabled from a deliberately small `capacity`, so
    the row tracks the cost of serving *through* a growth event (shape
    retrace + retile on the growth tick) rather than steady state only.
    """
    cfg = ServeConfig(n=n, deg=deg, graph=graph, landmarks=landmarks,
                      batches=ticks,
                      batch_size=batch_size, queries=queries, qps=qps,
                      microbatch=microbatch, pipeline=(mode != "sync"),
                      scenario=scenario or (
                          "growth" if mode == "growth" else "mixed"),
                      capacity=capacity, grow=(mode == "growth"),
                      backend=backend, block_v=block_v,
                      tile_shards=tile_shards, autotune=autotune,
                      quiet=True)
    rep = ServeLoop(cfg).run()
    warm = 2 if ticks > 2 else 1 if ticks > 1 else 0
    mbs = [m for m in rep.microbatches if m.tick >= warm]
    lat = np.concatenate([m.latencies for m in mbs])
    stale = float(np.concatenate(
        [np.full(m.latencies.shape, m.staleness) for m in mbs]).mean())
    upd = min(t.update_s for t in rep.ticks if t.tick >= warm)
    info = (f"ticks={ticks};Q={queries};qps={qps:g};mb={microbatch};"
            f"chunk={cfg.chunk_sweeps}")
    if mode == "growth":
        info += (f";growths={len(rep.growth)};cap={capacity}->"
                 f"{rep.final.graph.capacity}")
    rows = [emit(f"{name}/q_p50", float(np.percentile(lat, 50)), info),
            emit(f"{name}/q_p95", float(np.percentile(lat, 95)), info),
            emit(f"{name}/q_p99", float(np.percentile(lat, 99)), info),
            emit(f"{name}/update", upd, f"stat=min;{info}")]
    # Telemetry, not a latency: the value is mean versions-behind-head.
    row = f"{name}/staleness,{stale:.4f},unit=versions;{info}"
    print(row)
    rows.append(row)
    return rows


def _saturation_loop(name: str, n: int, deg: int, backend: str,
                     readers: int, landmarks: int, block_v: int,
                     tile_shards: int, microbatch: int,
                     slo_ms: float = 50.0, ticks: int = 3,
                     batch_size: int = 64,
                     autotune: bool = False) -> list[str]:
    """The replica-tier saturation row: ramp qps until p99 breaks the SLO.

    Deploys a real 1-updater + `readers`-reader topology (separate
    processes, the `launch/replica.py` router in front), lets the
    updater finish its ticks so the ramp measures serving alone, then
    drives open-loop client streams at a ×1.3 qps ladder. The row's
    value is the last rate the topology sustained with p99 <= `slo_ms`
    and <1% admission rejections — ``unit=qps;better=higher``, which
    `benchmarks/compare.py` gates with the inverted ratio. The ladder's
    coarseness is deliberate: one step of runner noise (−23%) stays
    inside the gate's 25% budget.
    """
    import shutil
    import sys
    import tempfile

    if jax.default_backend() == "tpu":
        # This process already holds the chip, and each replica process
        # would need one of its own (launch/replica.py refuses that).
        print(f"# {name} skipped: the replica tier needs one TPU chip per "
              f"process and this process holds the chip", file=sys.stderr)
        return []

    from repro.launch import replica
    from repro.launch.config import (EngineSpec, GraphSpec, ServeSpec,
                                     StreamSpec, TopologySpec)

    publish_dir = tempfile.mkdtemp(prefix="repro_sat_")
    spec = ServeSpec(
        graph=GraphSpec(n=n, deg=deg, landmarks=landmarks),
        engine=EngineSpec(backend=backend, block_v=block_v,
                          tile_shards=tile_shards, autotune=autotune),
        stream=StreamSpec(batches=ticks, batch_size=batch_size, queries=0,
                          microbatch=microbatch, quiet=True),
        topology=TopologySpec(readers=readers, slo_ms=slo_ms),
    )
    topo = replica.ReplicaTopology(spec, publish_dir)
    max_qps, p99_at_max = 0.0, 0.0
    try:
        topo.start()
        topo.updater.wait(timeout=300)  # ramp against a quiesced tier
        qps = 200.0
        while qps <= 8200.0:
            total = min(int(qps * 1.2), 4000)
            rep = replica.stream_queries(
                spec, topo, total, qps,
                workers=min(64, max(8, int(qps / 40))))
            p99 = rep.latency_percentiles()["p99"]
            if (p99 * 1e3 > slo_ms or not rep.answers
                    or rep.rejected > 0.01 * total):
                break
            max_qps, p99_at_max = qps, p99
            qps *= 1.3
    finally:
        topo.stop()
        shutil.rmtree(publish_dir, ignore_errors=True)
    row = (f"{name},{max_qps:.1f},unit=qps;better=higher;"
           f"readers={readers};slo_ms={slo_ms:g};mb={microbatch};"
           f"p99_at_max={p99_at_max * 1e3:.1f}ms")
    print(row)
    return [row]


def run(datasets=("ba_2k",), backends=("jnp", "pallas"),
        meshes=("none", "host"), ticks: int = 6, batch_size: int = 64,
        queries: int = 128, landmarks: int = 16, block_v: int = 256,
        tile_shards: int = 2, serve_modes=("sync", "pipeline"),
        qps: float = 2000.0, microbatch: int = 32) -> list[str]:
    rows = []
    crossover = None
    for ds in datasets:
        edges = DATASETS[ds]()
        n = int(edges[:, :2].max()) + 1
        cap = edges.shape[0] + ticks * batch_size + 64
        g0 = from_edges(n, edges, cap)
        lms = select_landmarks_by_degree(g0, landmarks)
        # The jnp-vs-tuned sweep comparison at this exact bench shape
        # (capacity slack included — that slack is where the tuned
        # sorted impl's win comes from), plus crossover bookkeeping.
        trows, speedup = _tune_rows(ds, g0, tile_shards, block_v)
        rows += trows
        if speedup > 1.0 and (crossover is None or n < crossover):
            crossover = n
        for backend in backends:
            for mesh_name in meshes:
                mesh = make_host_mesh() if mesh_name == "host" else None
                # pallas rows run autotuned: the row tracks the best
                # config the tuner finds on this runner, not a fixed
                # hand-picked tiling (impl lands in `derived`).
                rows += _tick_loop(f"ticks/{ds}/{backend}/{mesh_name}",
                                   g0, lms, edges, backend, mesh, ticks,
                                   batch_size, queries, block_v,
                                   tile_shards,
                                   autotune=(backend == "pallas"))
            # PR 10: frontier-proportional update rows (DESIGN.md §10) —
            # tick cost vs batch footprint with change propagation on.
            rows += _footprint_rows(ds, g0, lms, edges, backend, ticks,
                                    block_v, tile_shards,
                                    large=batch_size)
    # Telemetry, not a latency: smallest benched vertex count where the
    # tuned pallas config beat the jnp reference (0 = none did).
    row = (f"tune/crossover,{crossover or 0},unit=vertices;"
           f"datasets={'+'.join(datasets)}")
    print(row)
    rows.append(row)
    # The serving-pipeline trajectory: unsharded sync vs pipeline per
    # backend (the mesh × pipeline composition is smoke-tested by the CI
    # `mesh` job; benching it here would double the preset's runtime),
    # plus the grow-in-place trajectory: the `growth` scenario started
    # at a capacity that overflows on a *steady-state* tick, so the row
    # tracks query latency through the growth retrace/retile
    # (DESIGN.md §6) instead of only warm steady ticks.
    for ds in datasets:
        if ds not in SERVE_DATASETS:
            continue
        n, deg = BA_PARAMS[ds]
        e0 = DATASETS[ds]().shape[0]
        for backend in backends:
            for mode in serve_modes:
                # pallas serve rows run autotuned. The growth row stays
                # untuned: a re-tune fires inside every growth event
                # (capacity changes the table key), and putting tuner
                # compiles on the serving path would make the row track
                # compile noise instead of the growth cost.
                rows += _serve_loop(f"serve/{ds}/{backend}/{mode}", n, deg,
                                    backend, mode, ticks, batch_size,
                                    queries, landmarks, block_v,
                                    tile_shards, qps, microbatch,
                                    autotune=(backend == "pallas"))
            rows += _serve_loop(f"serve/{ds}/{backend}/growth", n, deg,
                                backend, "growth", ticks, batch_size,
                                queries, landmarks, block_v, tile_shards,
                                qps, microbatch,
                                capacity=e0 + 7 * batch_size // 2)
            # PR 8: the replica-tier saturation trajectory (DESIGN.md
            # §9) — how much client qps a real multi-process topology
            # (1 updater + R readers behind the coalescing router)
            # sustains inside the p99 SLO, for R=1 and R=2. The pair is
            # the scale-out story in two numbers: r2/r1 is the
            # throughput the second reader actually buys.
            for r in (1, 2):
                rows += _saturation_loop(
                    f"serve/{ds}/{backend}/max_qps_r{r}", n, deg,
                    backend, r, landmarks, block_v, tile_shards,
                    microbatch, autotune=(backend == "pallas"))
    # The weighted trajectory (DESIGN.md §8): tick rows on the road grid
    # (mesh composition is covered by the ba rows above; benching it
    # again on road would double the preset) and the `traffic` serving
    # rows — weight churn dominates each batch and every 4th tick is
    # weight-change-only, so the update row prices the no-retile path.
    road_edges = DATASETS["road_2k"]()
    road_n = int(road_edges[:, :2].max()) + 1
    road_cap = road_edges.shape[0] + ticks * batch_size + 64
    g0r = from_edges(road_n, road_edges, road_cap)
    lms_r = select_landmarks_by_degree(g0r, landmarks)
    for backend in backends:
        rows += _tick_loop(f"ticks/road_2k/{backend}/none", g0r, lms_r,
                           road_edges, backend, None, ticks, batch_size,
                           queries, block_v, tile_shards,
                           autotune=(backend == "pallas"))
        # Frontier footprint rows on the road grid too: the planar block
        # graph is where change propagation stays local (DESIGN.md §10)
        # and the trickle is the traffic scenario's weight-only tick.
        rows += _footprint_rows("road_2k", g0r, lms_r, road_edges,
                                backend, ticks, block_v, tile_shards,
                                large=batch_size)
        rows += _serve_loop(f"serve/road_2k/{backend}/traffic",
                            ROAD_PARAMS["road_2k"][0], 3, backend,
                            "pipeline", ticks, batch_size, queries,
                            landmarks, block_v, tile_shards, qps,
                            microbatch, autotune=(backend == "pallas"),
                            scenario="traffic", graph="road")
    return rows


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
