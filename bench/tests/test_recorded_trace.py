"""The reduction on a small trace recorded on a TPU v5e by
`record_trace.py`: the events of one traced tick of a tiny cell (pallas
sweeps, microbatches of 8). What the reduction read from them on the
chip is in `data/tiny_tpu.expected.json`; here it must read the same.
`load_events` itself is checked on a trace the CPU records here."""
import json
import os

import pytest

from benchlib import devicetrace as dt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "tiny_tpu.events.json")) as fh:
        events = [dt.Event(*row) for row in json.load(fh)]
    with open(os.path.join(DATA, "tiny_tpu.expected.json")) as fh:
        return events, json.load(fh)


def test_load_events_reads_host_spans(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(dt.SPAN_PREFIX + "probe"):
        jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = dt.load_events(path)
    assert [e.name for e in events] == ["bench.probe"]
    assert events[0].dur_ns > 0


def test_device_plane_and_spans(recorded):
    events, want = recorded
    assert {e.plane for e in events if dt.DEVICE_PLANE.match(e.plane)} \
        == {"/device:TPU:0"}
    spans = sorted({e.name for e in events
                    if not dt.DEVICE_PLANE.match(e.plane)})
    assert spans == want["spans"]
    assert "bench.query_microbatch" in spans and "bench.update" in spans


def test_reduction_matches_the_chip(recorded):
    events, want = recorded
    tr = dt.reduce(events, window_s=1.0)
    assert tr.chips == want["chips"] == 1
    assert 0 < tr.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert sorted(tr.programs) == want["programs"]
    assert len(tr.sweeps) == want["sweeps"] > 0
    assert sorted({s.program for s in tr.sweeps}) == want["sweep_programs"]
    assert tr.program_seconds(dt.BIBFS_PROGRAM) > 0
    assert tr.program_seconds(dt.UPDATE_PROGRAM) > 0


def test_busy_is_the_union_of_operations(recorded):
    events, _ = recorded
    ops = [e for e in events if e.line == dt.OPS_LINE]
    tr = dt.reduce(events, window_s=1.0)
    total = sum(e.dur_ns for e in ops) / 1e9
    # operations nest (a while holds its body), so the union is less
    # than the sum, and never more than first start to last end
    span = (max(e.end_ns for e in ops) - min(e.start_ns for e in ops)) / 1e9
    assert tr.busy_s < total and tr.busy_s <= span
    assert sum(tr.ops.values()) == pytest.approx(tr.busy_s, rel=1e-6)
