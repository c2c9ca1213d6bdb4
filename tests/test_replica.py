"""The replica serve tier, bottom-up: the router's QueryQueue policies
(admission control + microbatch coalescing) in isolation, the wire
protocol, the publish/ack barrier records, the ServeSpec config re-cut's
lossless round-trips — and the crash-recovery integration test: a reader
killed mid-stream, restarted from ``CURRENT``, with every answer checked
against the Dijkstra oracle *at the version it was served* and the
staleness ≤ 1 contract held across the process boundary (DESIGN.md §9).
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.launch import replica
from repro.launch.config import (EngineSpec, GraphSpec, ServeSpec,
                                 StreamSpec, TopologySpec, build_parser,
                                 spec_from_cli)
from repro.launch.replica import QueryQueue


# ---------------------------------------------------------------------------
# QueryQueue: admission control
# ---------------------------------------------------------------------------

def test_admission_counts_queries_not_requests():
    q = QueryQueue(max_pending=10, microbatch=32, coalesce_s=0.0)
    assert q.offer("a", 6)
    assert q.offer("b", 4)          # exactly at the cap
    assert q.pending == 10
    assert not q.offer("c", 1)      # one over: refused
    assert q.rejected == 1
    assert q.pending == 10          # refusal left the queue untouched


def test_admission_exempts_front_requeue():
    """A batch reclaimed from a dead reader re-enters at the head even
    when the queue is full — a reader crash must not surface as client
    rejections."""
    q = QueryQueue(max_pending=4, microbatch=32, coalesce_s=0.0)
    assert q.offer("a", 4)
    assert not q.offer("b", 1)
    assert q.offer("requeued", 3, front=True)
    assert q.pending == 7
    assert q.take() == ["requeued", "a"]  # head position preserved


def test_front_requeue_never_counts_as_rejected():
    """The rejected counter is admission refusals only: an exempt
    front-requeue past the cap must neither bump it nor unbalance the
    pending count across the eventual take."""
    q = QueryQueue(max_pending=4, microbatch=32, coalesce_s=0.0)
    assert q.offer("a", 4)
    assert not q.offer("b", 2)
    assert q.rejected == 2
    assert q.offer("r", 3, front=True)   # reclaimed batch
    assert q.rejected == 2               # exempt → uncounted
    assert q.pending == 7
    assert q.take() == ["r", "a"]
    assert q.pending == 0                # requeued queries fully drained


def test_coalesce_split_refusal_leaves_counters_intact():
    """When the next entry doesn't fit the open microbatch the coalescer
    refuses to split it and leaves it queued whole — that refusal is not
    an admission reject and must not leak pending queries."""
    q = QueryQueue(max_pending=100, microbatch=8, coalesce_s=0.01)
    q.offer("a", 6)
    q.offer("b", 5)                  # 6+5 > 8: left whole for next take
    assert q.take() == ["a"]
    assert q.pending == 5            # the refused entry is still accounted
    assert q.rejected == 0
    assert q.take() == ["b"]
    assert q.pending == 0


# ---------------------------------------------------------------------------
# Router counters (the stats doc the benchmarks and operators read)
# ---------------------------------------------------------------------------

def _router(tmp_path, microbatch=4, max_queue=2, readers=()):
    spec = ServeSpec(
        stream=StreamSpec(microbatch=microbatch, quiet=True),
        topology=TopologySpec(max_queue=max_queue))
    return replica.Router(spec, str(tmp_path), port=0,
                          reader_addrs=list(readers))


def test_router_counts_oversized_and_rejected_once(tmp_path):
    """Regression, two counter bugs in one client session: (a) the
    oversized-request REJECT path reported nothing at all, and (b) an
    admission refusal was counted twice — once by `QueryQueue.offer`,
    once again by the client loop. The stats doc must show each refusal
    exactly once, under its actual cause."""
    router = _router(tmp_path, microbatch=4, max_queue=2)
    client, server = socket.socketpair()
    t = threading.Thread(target=router._client_loop, args=(server,),
                         daemon=True)
    t.start()
    try:
        big = np.arange(6, dtype=np.int32)       # > microbatch
        replica.send_msg(client, replica.MSG_QUERY,
                         replica.pack_query(big, big))
        kind, _ = replica.recv_msg(client)
        assert kind == replica.MSG_REJECT
        two = np.arange(2, dtype=np.int32)       # fills max_queue exactly
        replica.send_msg(client, replica.MSG_QUERY,
                         replica.pack_query(two, two))
        one = np.arange(1, dtype=np.int32)       # one over: refused
        replica.send_msg(client, replica.MSG_QUERY,
                         replica.pack_query(one, one))
        kind, _ = replica.recv_msg(client)
        assert kind == replica.MSG_REJECT
        replica.send_msg(client, replica.MSG_STATS)
        kind, payload = replica.recv_msg(client)
        assert kind == replica.MSG_STATS
        stats = json.loads(payload)
        assert stats["oversized"] == 6           # queries, its own cause
        assert stats["rejected"] == 1            # once, owned by the queue
        assert router.queue.rejected == 1
        assert stats["pending"] == 2             # the admitted entry
    finally:
        replica.send_msg(client, replica.MSG_STOP)
        t.join(timeout=5.0)
        client.close()


def test_router_requeued_counts_queries_not_entries(tmp_path):
    """Regression: the dead-reader requeue path bumped `requeued` by
    len(batch) — entries — while every other stat is query-denominated.
    One reclaimed 3-query batch must count as 3."""
    srv = socket.create_server(("127.0.0.1", 0))
    addr = srv.getsockname()

    def accept_and_drop():
        conn, _ = srv.accept()
        replica.recv_msg(conn)       # take the dispatched batch...
        srv.close()                  # no reconnect: one failure exactly
        conn.close()                 # ...and die before answering

    threading.Thread(target=accept_and_drop, daemon=True).start()
    router = _router(tmp_path, microbatch=8, max_queue=16, readers=[addr])
    qs = np.arange(3, dtype=np.int32)
    entry = replica._Entry(None, threading.Lock(), qs, qs)
    assert router.queue.offer(entry, qs.size)
    t = threading.Thread(target=router._dispatch_loop, args=(0,),
                         daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with router._stats_lock:
                if router.stats["requeued"]:
                    break
            time.sleep(0.01)
        assert router.stats["requeued"] == 3     # queries, not 1 entry
        assert router.stats["reader_errors"][0] == 1
        assert router.queue.pending == 3         # reclaimed at the head
    finally:
        router.running = False
        t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# QueryQueue: coalescing
# ---------------------------------------------------------------------------

def test_coalesce_merges_up_to_microbatch():
    q = QueryQueue(max_pending=100, microbatch=8, coalesce_s=10.0)
    for name, m in (("a", 3), ("b", 3), ("c", 2), ("d", 1)):
        q.offer(name, m)
    # 3+3+2 fills the microbatch exactly; "d" stays for the next take —
    # and a full batch returns without waiting out the 10s window.
    t0 = time.monotonic()
    assert q.take() == ["a", "b", "c"]
    assert time.monotonic() - t0 < 5.0
    assert q.pending == 1


def test_coalesce_never_splits_entries():
    """Entries are whole client requests — each must be answered at one
    version, so the coalescer takes them entirely or not at all."""
    q = QueryQueue(max_pending=100, microbatch=8, coalesce_s=0.01)
    q.offer("a", 5)
    q.offer("b", 5)                  # 5+5 > 8: must not be split
    assert q.take() == ["a"]
    assert q.take() == ["b"]


def test_coalesce_dispatches_oversized_alone():
    q = QueryQueue(max_pending=100, microbatch=8, coalesce_s=0.01)
    q.offer("big", 20)               # admitted (<=max_pending), > microbatch
    q.offer("small", 1)
    assert q.take() == ["big"]       # oversized runs alone
    assert q.take() == ["small"]


def test_coalesce_window_closes_on_partial_batch():
    q = QueryQueue(max_pending=100, microbatch=32, coalesce_s=0.05)
    q.offer("a", 2)
    t0 = time.monotonic()
    assert q.take(timeout=5.0) == ["a"]
    assert time.monotonic() - t0 < 2.0   # window (50ms), not timeout (5s)


def test_take_empty_after_timeout():
    q = QueryQueue(max_pending=10, microbatch=8, coalesce_s=0.01)
    assert q.take(timeout=0.01) == []


def test_take_picks_up_late_arrivals_inside_window():
    q = QueryQueue(max_pending=100, microbatch=8, coalesce_s=0.5)
    got = []
    t = threading.Thread(target=lambda: got.extend(q.take(timeout=2.0)))
    q.offer("a", 2)
    t.start()
    time.sleep(0.05)
    q.offer("b", 2)                  # lands inside the open window
    t.join()
    assert got == ["a", "b"]


# ---------------------------------------------------------------------------
# Wire protocol
# ---------------------------------------------------------------------------

def test_query_answer_pack_roundtrip():
    qs = np.arange(5, dtype=np.int32)
    qt = np.arange(5, 10, dtype=np.int32)
    qs2, qt2 = replica.unpack_query(replica.pack_query(qs, qt))
    np.testing.assert_array_equal(qs, qs2)
    np.testing.assert_array_equal(qt, qt2)
    v, h, d = replica.unpack_answer(
        replica.pack_answer(7, 8, np.asarray([1, 2, 3], np.int32)))
    assert (v, h) == (7, 8)
    np.testing.assert_array_equal(d, [1, 2, 3])


# ---------------------------------------------------------------------------
# Publish/ack records (the barrier's inputs)
# ---------------------------------------------------------------------------

def test_publish_requires_saved_step(tmp_path):
    d = str(tmp_path)
    with pytest.raises(FileNotFoundError):
        ckpt.publish(d, 3)
    ckpt.save(d, 3, {"x": np.arange(4)})
    rec = ckpt.publish(d, 3)
    assert rec["version"] == 3
    assert ckpt.current_step(d) == 3


def test_prune_never_removes_published_step(tmp_path):
    d = str(tmp_path)
    for s in range(5):
        ckpt.save(d, s, {"x": np.arange(4) + s})
    ckpt.publish(d, 1)
    ckpt.prune(d, keep=2)
    assert ckpt.current_step(d) == 1
    assert ckpt.step_manifest(d, 1) is not None      # published: protected
    assert ckpt.step_manifest(d, 4) is not None      # newest: kept
    assert ckpt.step_manifest(d, 0) is None          # pruned


def test_prune_keeps_steps_between_current_and_latest(tmp_path):
    """Regression: prune protected only the step CURRENT names, so with
    an old pointer and an aggressive keep it deleted the steps between
    CURRENT and the head — breaking a reader that loaded CURRENT and is
    replaying forward to catch up. The whole [CURRENT, latest] range
    must survive."""
    d = str(tmp_path)
    for s in range(6):
        ckpt.save(d, s, {"x": np.arange(4) + s})
    ckpt.publish(d, 2)                               # pointer lags the head
    ckpt.prune(d, keep=1)
    for s in range(2, 6):                            # published..latest
        assert ckpt.step_manifest(d, s) is not None, s
    assert ckpt.step_manifest(d, 0) is None          # strictly older: pruned
    assert ckpt.step_manifest(d, 1) is None
    # No pointer yet: plain newest-k retention still applies.
    d2 = str(tmp_path / "unpublished")
    for s in range(3):
        ckpt.save(d2, s, {"x": np.arange(4) + s})
    ckpt.prune(d2, keep=1)
    assert ckpt.step_manifest(d2, 2) is not None
    assert ckpt.step_manifest(d2, 0) is None


def test_ack_barrier_ignores_dead_readers(tmp_path):
    d = str(tmp_path)
    replica.write_ack(d, 0, version=5)               # live: this process
    # A pid that has definitely exited: a finished child.
    p = subprocess.Popen(["true"])
    p.wait()
    replica.write_ack(d, 1, version=0)
    acks = replica.read_acks(d)
    rec = dict(acks[1])
    rec["pid"] = p.pid
    ckpt.write_json_atomic(
        os.path.join(d, "acks", "reader_1.json"), rec)
    # Reader 1 is behind but dead — the barrier must not wait for it.
    assert replica.wait_for_acks(d, version=5, timeout_s=5.0)


def test_ack_barrier_times_out_on_live_laggard(tmp_path):
    d = str(tmp_path)
    replica.write_ack(d, 0, version=1)               # live (us), behind
    t0 = time.monotonic()
    assert not replica.wait_for_acks(d, version=2, timeout_s=0.1,
                                     log=lambda *a: None)
    assert time.monotonic() - t0 < 2.0


# ---------------------------------------------------------------------------
# ServeSpec round-trips (the config re-cut's losslessness contract)
# ---------------------------------------------------------------------------

def _nondefault_spec() -> ServeSpec:
    return ServeSpec(
        graph=GraphSpec(n=500, deg=3, landmarks=8, capacity=640, grow=True),
        engine=EngineSpec(backend="pallas", block_v=128, frontier=True),
        stream=StreamSpec(batches=3, qps=123.5, pipeline=True, verify=True),
        topology=TopologySpec(readers=3, coalesce_ms=5.0, restart=True))


def test_spec_cli_roundtrip():
    spec = _nondefault_spec()
    ap = build_parser("t")
    ns = ap.parse_args(spec.to_args())
    assert ServeSpec.from_parsed_args(ns) == spec


def test_spec_json_roundtrip(tmp_path):
    spec = _nondefault_spec()
    path = str(tmp_path / "spec.json")
    spec.save_json(path)
    assert ServeSpec.load_json(path) == spec


def test_spec_serve_config_roundtrip():
    spec = _nondefault_spec()
    cfg = spec.to_serve_config()
    assert cfg.n == 500 and cfg.backend == "pallas" and cfg.qps == 123.5
    back = ServeSpec.from_serve_config(cfg, topology=spec.topology)
    assert back == spec


def test_flat_flags_alone_are_the_spec():
    ap = build_parser("t")
    ns = ap.parse_args(["--n", "700"])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spec = spec_from_cli(ns, ap)
    assert spec.graph.n == 700
    assert not w                     # flat-only: supported, no warning


def test_flat_overrides_alongside_config_warn_deprecated(tmp_path):
    path = str(tmp_path / "spec.json")
    _nondefault_spec().save_json(path)
    ap = build_parser("t")
    ns = ap.parse_args(["--config", path, "--n", "700"])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        spec = spec_from_cli(ns, ap)
    assert spec.graph.n == 700               # flat flag overrode the JSON
    assert spec.engine.backend == "pallas"   # the rest came from the JSON
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


def test_realized_n_road_rounds_to_grid():
    import math
    gs = GraphSpec(n=2025, graph="road")
    rows = max(2, math.isqrt(2025))
    assert gs.realized_n() == rows * max(2, (2025 + rows - 1) // rows)
    assert GraphSpec(n=2025).realized_n() == 2025


# ---------------------------------------------------------------------------
# Crash recovery: kill a reader mid-stream, restart from CURRENT,
# zero wrong answers at each answer's served version, staleness <= 1.
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# One process per chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chips,readers", [(1, 1), (4, 4)])
def test_topology_refuses_more_processes_than_chips(tmp_path, monkeypatch,
                                                    chips, readers):
    """On a TPU host the topology fails fast, before spawning anything,
    when 1 updater + N readers outnumber the chips — instead of leaving
    the surplus processes to hang on the TPU library's lock."""
    monkeypatch.setattr(replica, "host_tpu_chips", lambda: chips)
    spawned = []
    monkeypatch.setattr(replica.ReplicaTopology, "_spawn",
                        lambda self, *a: spawned.append(a))
    spec = ServeSpec(topology=TopologySpec(readers=readers))
    topo = replica.ReplicaTopology(spec, str(tmp_path / "pub"))
    with pytest.raises(replica.ChipOversubscribedError) as err:
        topo.start()
    assert (err.value.processes, err.value.chips) == (readers + 1, chips)
    assert not spawned and not (tmp_path / "pub").exists()


def test_host_tpu_chips_off_tpu(monkeypatch):
    """A host whose JAX platform list names no TPU holds no chips for the
    topology, whatever device nodes exist."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert replica.host_tpu_chips() == 0


def test_serve_parent_stays_off_jax():
    """The serve role's parent imports neither JAX nor a module that
    does: on a TPU host its children hold the chips."""
    code = ("import sys; import repro.launch.replica, repro.launch.config;"
            " sys.exit('jax' in sys.modules)")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    assert subprocess.run([sys.executable, "-c", code],
                          env=env).returncode == 0


@pytest.mark.slow
def test_reader_crash_recovery(tmp_path):
    spec = ServeSpec(
        graph=GraphSpec(n=300, deg=3, landmarks=8),
        stream=StreamSpec(batches=3, batch_size=30, queries=0,
                          microbatch=16, seed=3, quiet=True),
        topology=TopologySpec(readers=2, restart=True))
    topo = replica.ReplicaTopology(spec, str(tmp_path))
    killed = [False]

    def kill_once():
        # Mid-stream, not at the edges: the victim is likely holding an
        # in-flight batch, which must be requeued and answered elsewhere.
        if not killed[0] and time.monotonic() > t_kill[0]:
            killed[0] = True
            topo.kill_reader(0)

    try:
        topo.start()
        t_kill = [time.monotonic() + 1.0]
        report = replica.stream_queries(spec, topo, total=240, qps=120.0,
                                        on_tick=kill_once)
        assert killed[0]
        assert topo.updater_ok()
        assert topo.reader_restarts >= 1
        # No client-visible loss: every query either answered or (at
        # most transiently, while one reader was down) rejected.
        assert len(report.answers) + report.rejected == 240
        assert len(report.answers) >= 200
        assert report.max_staleness() <= 1
        # The heart of the contract: zero wrong answers, each checked
        # against Dijkstra on the graph at the version that served it.
        assert replica.verify_answers(str(tmp_path), report.answers) == 0
    finally:
        topo.stop()
