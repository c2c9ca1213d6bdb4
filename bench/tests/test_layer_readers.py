"""The readers of the serve loop's own spans and counters: after a tiny
cell run on the CPU each returns a finite value in range, and where the
program keeps no record of the window (a program without its spans and
counters, or the record of another run) each returns nothing."""
import math
import sys

import numpy as np
import pytest

from benchlib import cells, harness
from repro.launch import trace
from tinycell import run_tiny, tiny_cell

READERS = ("host_prep_s_per_tick.churn", "query_wait_p90_s.churn",
           "construct_s", "bibfs_waves_per_mb.sat", "bibfs_lane_share.sat")


def in_range(name: str, value: float, w: harness.Window,
             rec: trace.RunRecord) -> bool:
    tick_s = [sum(h.values()) for h in rec.host_s]
    return {
        "host_prep_s_per_tick.churn": 0 < value <= max(tick_s),
        "query_wait_p90_s.churn": 0 <= value <= w.latencies.max(),
        "construct_s": value == pytest.approx(sum(rec.construct_s.values()))
        and value > 0,
        "bibfs_waves_per_mb.sat": 0 < value <= 64,
        "bibfs_lane_share.sat": 0 < value <= 100,
    }[name]


@pytest.fixture(scope="module")
def window():
    kept = []

    class Kept(harness.Window):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            kept.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_compile_cache", lambda: None)
        mp.setattr(harness, "Window", Kept)
        res = run_tiny(tiny_cell())
    assert res["correct"], res["checks"]
    (w,) = kept
    return w, trace.last_run()


@pytest.mark.parametrize("name", READERS)
def test_reader_in_range(window, name):
    w, rec = window
    value = cells.metric_reader(name)(w)
    assert value is not None and math.isfinite(value)
    assert in_range(name, value, w, rec), value


def _other_run():
    return trace.RunRecord(
        host_s=({"serve.prepare.fold": 0.1}, {"serve.prepare.fold": 0.1}),
        microbatches=(trace.MicrobatchHost(2, 0.1, 3, 4),),
        construct_s={"serve.construct.load": 1.0})


NO_RECORD = {
    "no_trace_module": lambda mp: mp.setitem(
        sys.modules, "repro.launch.trace", None),
    "no_last_run": lambda mp: mp.delattr(trace, "last_run"),
    "nothing_published": lambda mp: mp.setattr(trace, "_last_run", None),
    "another_run": lambda mp: mp.setattr(trace, "_last_run", _other_run()),
}


@pytest.mark.parametrize("case", NO_RECORD)
@pytest.mark.parametrize("name", READERS)
def test_reader_silent_without_program_records(monkeypatch, name, case):
    """A program without the spans and counters, or a record that is not
    the window's, gives no value and no error."""
    NO_RECORD[case](monkeypatch)
    w = harness.Window(
        ticks=1, answered=2, latencies=np.array([0.5, 0.7]),
        staleness=np.zeros(2, np.int32), updates=4, live_edges=[10, 10],
        vertices=16, landmarks=2, microbatch=8)
    assert cells.metric_reader(name)(w) is None
