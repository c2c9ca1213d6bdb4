"""The serve loop's own spans and counters: the recorder's self time and
per-tick fold (`launch/trace.py`), its clock against the profiler's, and
the records a tiny serve loop keeps in both serving modes (host seconds
per tick, service time per microbatch, BiBFS waves and live lanes)."""
from __future__ import annotations

import collections
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro.launch import serve, trace
from repro.launch.serve import ServeConfig, ServeLoop
from repro.launch.trace import SpanRecorder

PREPARE = ("serve.prepare.snapshot_edges", "serve.prepare.draw_updates",
           "serve.prepare.make_batch", "serve.prepare.queries",
           "serve.prepare.capacity", "serve.prepare.retile",
           "serve.prepare.fold", "serve.prepare.stats")
CONSTRUCT = ("serve.construct.generate", "serve.construct.load",
             "serve.construct.landmarks", "serve.construct.tile",
             "serve.construct.label", "serve.construct.index")


def _dur(sp) -> int:
    return sp.end_ns - sp.start_ns


# --- the recorder -----------------------------------------------------------

def test_self_time_is_duration_less_children():
    rec = SpanRecorder()
    with rec.span("a") as a:
        time.sleep(0.002)
        with rec.span("b") as b1:
            time.sleep(0.002)
            with rec.span("c") as c:
                time.sleep(0.002)
        with rec.span("b") as b2:
            time.sleep(0.002)
    assert [sp.name for sp in rec.spans] == ["c", "b", "b", "a"]
    assert c.self_ns == _dur(c) > 0
    assert b1.self_ns == _dur(b1) - _dur(c)
    assert b2.self_ns == _dur(b2)
    assert a.self_ns == _dur(a) - _dur(b1) - _dur(b2) > 0
    # a child lies inside its parent
    assert a.start_ns <= b1.start_ns <= c.start_ns <= c.end_ns <= b1.end_ns \
        <= b2.start_ns <= b2.end_ns <= a.end_ns


def test_take_folds_self_time_by_name_and_clears():
    rec = SpanRecorder()
    with rec.span("serve.tick", tick=0) as tick:
        for i in range(3):
            with rec.span("serve.prepare.x", i=i):
                time.sleep(0.001)
        with rec.span("serve.prepare.y"):
            pass
        with rec.span("serve.update"):
            time.sleep(0.001)
    spans = list(rec.spans)
    assert rec.seconds("serve.prepare.") == pytest.approx(
        sum(sp.self_ns for sp in spans
            if sp.name.startswith("serve.prepare.")) / 1e9)
    host_s = rec.take()
    assert set(host_s) == {"serve.tick", "serve.prepare.x",
                           "serve.prepare.y", "serve.update"}
    for name, secs in host_s.items():
        assert secs == pytest.approx(
            sum(sp.self_ns for sp in spans if sp.name == name) / 1e9)
    # self times of a span tree add up to the root's duration
    assert sum(host_s.values()) == pytest.approx(tick.seconds)
    assert rec.spans == [] and rec.take() == {}


def test_spans_share_the_profiler_clock(tmp_path):
    """Each `serve.*` annotation in the profile has an in-memory span of
    the same name that starts and lasts the same, within 2 ms."""
    from jax.profiler import ProfileData

    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("serve.tick", tick=0):
            for i in range(3):
                with rec.span("serve.microbatch", tick=0, mb=i):
                    jnp.arange(8).sum().block_until_ready()
                    time.sleep(0.005)
            with rec.span("serve.wait", tick=0):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    mine = list(rec.spans)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    profile = ProfileData.from_file(path)
    planes = list(profile.planes)
    # the timeline's offsets count from the profile's start, in epoch ns
    (origin,) = [dict(p.stats)["profile_start_time"] for p in planes
                 if "profile_start_time" in dict(p.stats)]
    theirs = [(ev.name, origin + ev.start_ns, ev.duration_ns)
              for p in planes for line in p.lines for ev in line.events
              if ev.name.startswith("serve.")]
    assert sorted(n for n, _, _ in theirs) == sorted(sp.name for sp in mine)
    for name, start, dur in theirs:
        near = min((sp for sp in mine if sp.name == name),
                   key=lambda sp: abs(sp.start_ns - start))
        assert abs(near.start_ns - start) < 2e6, name
        assert abs(_dur(near) - dur) < 2e6, name


# --- the serve loop's records -----------------------------------------------

def _tiny(pipeline: bool, batches: int = 2) -> ServeConfig:
    return ServeConfig(n=200, deg=3, landmarks=8, batches=batches,
                       batch_size=20, queries=24, qps=5000.0, microbatch=8,
                       pipeline=pipeline, quiet=True)


@pytest.fixture(scope="module", params=[False, True],
                ids=["sync", "pipeline"])
def served(request):
    t0 = time.time()
    rep = ServeLoop(_tiny(request.param)).run()
    return rep, time.time() - t0


def test_tick_host_seconds_name_every_phase(served):
    rep, wall = served
    update = "serve.update_chunk" if rep.config.pipeline else "serve.update"
    for t in rep.ticks:
        assert set(PREPARE) | {"serve.tick", "serve.apply_batch", update,
                               "serve.commit",
                               "serve.microbatch"} <= set(t.host_s)
        assert all(v >= 0 for v in t.host_s.values())
        assert sum(t.host_s[k] for k in PREPARE) <= sum(t.host_s.values())
    # each tick's self seconds add up to its wall time, inside the run's
    assert sum(sum(t.host_s.values()) for t in rep.ticks) <= wall
    assert set(rep.construct_s) == set(CONSTRUCT)
    assert all(v >= 0 for v in rep.construct_s.values())


def test_service_time_within_every_latency(served):
    rep, _ = served
    assert rep.microbatches
    for m in rep.microbatches:
        assert 0 <= m.service_s <= m.latencies.min()


def test_live_lane_waves_within_waves(served):
    rep, _ = served
    for m in rep.microbatches:
        assert 0 < m.waves <= 64
        assert 0 <= m.live_lane_waves <= m.waves * m.qs.shape[0]


def test_unit_weight_microbatches_take_the_bit_packed_path(served):
    rep, _ = served
    assert rep.microbatches
    assert all(m.bit_packed is True for m in rep.microbatches)


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipeline"])
def test_capped_search_counts_no_waves(monkeypatch, pipeline):
    """The benchmark's control (every BiBFS capped at 0 waves) runs none."""
    orig = serve.batched_query
    monkeypatch.setattr(serve, "batched_query", lambda *a, **kw: orig(
        *a, **{**kw, "max_steps": 0}))
    rep = ServeLoop(_tiny(pipeline, batches=1)).run()
    assert rep.microbatches
    assert all(m.waves == 0 and m.live_lane_waves == 0
               for m in rep.microbatches)


def test_finished_run_publishes_its_host_records():
    """The latest finished run's host records outlive its loop and report,
    and match them."""
    rep = ServeLoop(_tiny(False, batches=1)).run()
    rec = trace.last_run()
    assert rec.host_s == tuple(t.host_s for t in rep.ticks)
    assert rec.construct_s == rep.construct_s
    assert rec.microbatches == tuple(
        (m.qs.shape[0], m.service_s, m.waves, m.live_lane_waves,
         m.bit_packed, m.between_chunks)
        for m in rep.microbatches)
    assert rec.update_chunks == tuple(t.update_chunks for t in rep.ticks)


# --- the pipelined update's counters ----------------------------------------

PHASES = {"search-seed", "search", "repair-seed", "repair", "finish"}


class KeepingRecorder(SpanRecorder):
    """Keeps the (name, tag) of every span each `take()` clears: the first
    take is construction's, then one per tick."""

    def __init__(self):
        super().__init__()
        self.taken: list[list[tuple[str, str | None]]] = []

    def take(self):
        self.taken.append([(sp.name, sp.ids.get("tag"))
                           for sp in self.spans])
        return super().take()


@pytest.fixture(scope="module", params=[False, True],
                ids=["sync", "pipeline"])
def kept(request):
    loop = ServeLoop(_tiny(request.param, batches=3))
    loop.trace = KeepingRecorder()
    rep = loop.run()
    return rep, loop.trace.taken[1:], trace.last_run()


def test_update_dispatches_counted_by_phase(kept):
    """Per tick, the dispatch counts by phase tag are the tick's
    `serve.update_chunk` spans by tag: one of each seed and the finish,
    search and repair chunks between. The sync path counts none."""
    rep, per_tick, _ = kept
    assert len(per_tick) == len(rep.ticks) == 3
    for t, spans in zip(rep.ticks, per_tick):
        chunk_tags = collections.Counter(
            tag for name, tag in spans if name == "serve.update_chunk")
        assert t.update_chunks == dict(chunk_tags)
        if not rep.config.pipeline:
            assert t.update_chunks == {}
            continue
        assert set(t.update_chunks) <= PHASES
        assert all(t.update_chunks[p] == 1
                   for p in ("search-seed", "repair-seed", "finish"))
        assert sum(t.update_chunks.values()) == len(
            [n for n, _ in spans if n == "serve.update_chunk"])


def test_between_chunks_marks_the_stale_microbatches(kept):
    """A microbatch is marked between chunks exactly when it was served
    one version behind the head; on the sync path none is."""
    rep, _, _ = kept
    assert rep.microbatches
    for m in rep.microbatches:
        assert m.between_chunks == (m.staleness == 1)
    if rep.config.pipeline:
        assert any(m.between_chunks for m in rep.microbatches)
    else:
        assert not any(m.between_chunks for m in rep.microbatches)


def test_last_run_carries_the_pipeline_counters(kept):
    rep, _, rec = kept
    assert rec.update_chunks == tuple(t.update_chunks for t in rep.ticks)
    assert [m.between_chunks for m in rec.microbatches] == \
        [m.between_chunks for m in rep.microbatches]


def test_one_microbatch_between_two_chunks(kept):
    """Between two update dispatches the loop serves one microbatch at
    most (the rest of the arrived queries wait for the next chunk's
    end), and those are the microbatches marked `between_chunks`."""
    rep, per_tick, _ = kept
    for t, spans in zip(rep.ticks, per_tick):
        order = [n for n, _ in spans
                 if n in ("serve.update_chunk", "serve.microbatch")]
        if not rep.config.pipeline:
            assert "serve.update_chunk" not in order
            continue
        last = len(order) - 1 - order[::-1].index("serve.update_chunk")
        inside = order[order.index("serve.update_chunk"):last + 1]
        assert "serve.microbatch serve.microbatch" not in " ".join(inside)
        marked = sum(m.between_chunks for m in rep.microbatches
                     if m.tick == t.tick)
        assert marked == inside.count("serve.microbatch")
        assert marked <= sum(t.update_chunks.values()) - 1
