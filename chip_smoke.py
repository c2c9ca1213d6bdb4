#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # phases A + B on one chip
    python chip_smoke.py --four-chips  # the mesh path only, on four chips

Everything runs in this one process, through the serve loop a user runs
(`ServeSpec` -> `ServeLoop`), at deployment size: a Barabási–Albert graph
of 2^20 vertices (attachment degree 4), R = 32 landmarks, slots
provisioned for the stream, then `--ticks` ticks of the `mixed` scenario
(1024 updates and 256 queries per tick, microbatches of 32, pipeline off).

* Phase A serves the stream on `backend=pallas` with the min-plus bound
  kernel on. It asserts the engine kept the pallas backend and that both
  kernels lower to compiled TPU kernels (`tpu_custom_call`).
* Phase B serves the same stream on `backend=jnp`. The final labelling
  (dist, hub, highway) and every answer must be bit-identical to A.
* `--four-chips` runs only the mesh path (`mesh=host`, `shards=2`:
  data=2 x model=2 over four devices, backend pallas) and compares it with
  the unsharded pallas run of the same stream on one device.

Each tick's first 16 answers are checked against a breadth-first search
in scipy (the BA graph has w = 1), run on the snapshot each answer was
served at. The wall times printed are smoke timings, not benchmark
numbers. Any failure exits non-zero before the last line; on success the
last line is the JSON contract line
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
It exits non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ORACLE_PAIRS = 16  # answers checked against the BFS oracle per tick


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--deg", type=int, default=4)
    ap.add_argument("--landmarks", type=int, default=32)
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--microbatch", type=int, default=32)
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the query stream")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh path (data=2 x model=2) and "
                         "the unsharded run it is compared with")
    return ap.parse_args(argv)


class CompileClock:
    """Seconds the backend spent compiling, summed from JAX's own
    compile-duration events (tracing is not counted: its events nest)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += secs


def serve(args, clock: CompileClock, label: str, **engine):
    """One serve-loop run of the smoke stream; returns (loop, report)."""
    from repro.launch.config import (EngineSpec, GraphSpec, ServeSpec,
                                     StreamSpec)
    from repro.launch.serve import ServeLoop

    spec = ServeSpec(
        graph=GraphSpec(n=args.n, deg=args.deg, landmarks=args.landmarks),
        engine=EngineSpec(**engine),
        stream=StreamSpec(batches=args.ticks, batch_size=args.batch_size,
                          scenario="mixed", queries=args.queries,
                          microbatch=args.microbatch, seed=args.seed))
    loop = ServeLoop(spec.to_serve_config(keep_history=True))
    c0, t0 = clock.total, time.perf_counter()
    report = loop.run()
    wall = time.perf_counter() - t0
    compile_s = clock.total - c0
    ticks = " ".join(f"{t.update_s:.3f}" for t in report.ticks)
    print(f"smoke timing (not a benchmark number) {label}: wall "
          f"{wall:.3f}s, of which compile {compile_s:.3f}s; "
          f"update per tick [{ticks}]s", flush=True)
    return loop, report


def answers(report):
    import numpy as np
    return np.concatenate([m.answers for m in report.microbatches])


def check_oracle(report) -> int:
    """Check each tick's first ORACLE_PAIRS answers against a scipy BFS on
    the snapshot they were served at; returns the mismatch count."""
    import numpy as np
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import shortest_path

    from repro.graphs.coo import INF_D

    wrong = 0
    for tick in sorted({m.tick for m in report.microbatches}):
        pairs = [(m.version, int(s), int(t), int(d))
                 for m in report.microbatches if m.tick == tick
                 for s, t, d in zip(m.qs, m.qt, m.answers)][:ORACLE_PAIRS]
        for version in sorted({p[0] for p in pairs}):
            g = report.history[version].graph
            live = np.asarray(g.valid)
            src, dst = np.asarray(g.src)[live], np.asarray(g.dst)[live]
            adj = csr_matrix((np.ones(src.size, np.int8), (src, dst)),
                             shape=(g.n, g.n))
            sources = sorted({p[1] for p in pairs if p[0] == version})
            dist = shortest_path(adj, method="D", unweighted=True,
                                 indices=sources)
            row = {s: i for i, s in enumerate(sources)}
            for v, s, t, got in pairs:
                if v != version:
                    continue
                want = dist[row[s], t]
                ok = (got >= int(INF_D)) if np.isinf(want) \
                    else got == int(want)
                wrong += not ok
        print(f"oracle tick {tick}: checked {len(pairs)} pairs, {wrong} "
              f"mismatches so far", flush=True)
    return wrong


def assert_same(a_report, b_report, what: str) -> None:
    import numpy as np
    fa, fb = a_report.final.labelling, b_report.final.labelling
    for field in ("dist", "hub", "highway"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fa, field)), np.asarray(getattr(fb, field)),
            err_msg=f"{what}: final labelling `{field}` differs")
    np.testing.assert_array_equal(answers(a_report), answers(b_report),
                                  err_msg=f"{what}: answers differ")
    print(f"{what}: final labelling and {answers(a_report).size} answers "
          f"bit-identical", flush=True)


def assert_compiled_kernels(loop, report) -> None:
    """Both Pallas kernels of the pallas run lower to TPU kernels."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import relax_sweep
    from repro.core.query import query_upper_bound
    from repro.graphs.coo import INF_D

    assert loop.engine.backend == "pallas", loop.engine.backend
    snap = report.final
    assert snap.plan.backend == "pallas" and snap.plan.impl == "kernel", \
        (snap.plan.backend, snap.plan.impl)
    keys = jnp.zeros((snap.graph.n,), jnp.int32)
    sweep = jax.jit(lambda k: relax_sweep(snap.plan, snap.graph, k, 1,
                                          INF_D)).lower(keys).as_text()
    q = jnp.zeros((loop.cfg.microbatch,), jnp.int32)
    bound = jax.jit(lambda s, t: query_upper_bound(
        snap.labelling, s, t, use_kernel=True)).lower(q, q).as_text()
    for name, text in (("edge_relax sweep", sweep), ("minplus bound", bound)):
        assert "tpu_custom_call" in text, f"{name} is not a TPU kernel"
    print("kernels: edge_relax sweep and minplus bound lower to "
          "tpu_custom_call", flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke run needs one",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = CompileClock()
    common = dict(backend="pallas", use_minplus_kernel=True)
    if args.four_chips:
        loop, a = serve(args, clock, "mesh data=2 x model=2 pallas",
                        mesh="host", shards=2, **common)
        assert loop.mesh is not None and loop.mesh.size == 4
    else:
        loop, a = serve(args, clock, "phase A pallas", **common)
    assert_compiled_kernels(loop, a)
    bad = check_oracle(a)
    # Only the final labelling and the answers are compared from here on:
    # free the first run's older snapshots before the second run.
    del loop
    a.history.clear()
    if args.four_chips:
        _, b = serve(args, clock, "unsharded pallas", **common)
        assert_same(a, b, "sharded vs unsharded")
    else:
        _, b = serve(args, clock, "phase B jnp", backend="jnp")
        assert_same(a, b, "pallas vs jnp")
    if bad:
        print(f"chip_smoke: {bad} oracle mismatches", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
