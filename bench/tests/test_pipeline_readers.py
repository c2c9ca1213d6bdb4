"""The readers of the pipelined update's counters
(`update_chunks_per_batch.stale1`, `chunk_s.stale1`,
`interleaved_mb_per_tick.stale1`): values after a tiny pipelined cell run
on the CPU, values on hand-made records and traces, and nothing where the
program keeps no record of the window, does not count the dispatches, or
serves synchronously."""
from typing import NamedTuple

import numpy as np
import pytest

from benchlib import cells, devicetrace, harness
from repro.launch import trace
from test_layer_readers import NO_RECORD
from tinycell import run_tiny, tiny_cell

CHUNKS = "update_chunks_per_batch.stale1"
CHUNK_S = "chunk_s.stale1"
INTERLEAVED = "interleaved_mb_per_tick.stale1"
READERS = (CHUNKS, CHUNK_S, INTERLEAVED)


@pytest.fixture(scope="module")
def stale_window():
    kept = []

    class Kept(harness.Window):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            kept.append(self)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_compile_cache", lambda: None)
        mp.setattr(harness, "Window", Kept)
        res = run_tiny(tiny_cell("churn", serving={"pipeline": True},
                                 guarantee={"max_staleness": 1}))
    assert res["correct"], res["checks"]
    (w,) = kept
    return w, trace.last_run()


def test_pipelined_cell_counts_its_dispatches(stale_window):
    w, rec = stale_window
    value = cells.metric_reader(CHUNKS)(w)
    # each tick: both seeds, the finish, and at least one chunk of each
    # fixpoint (the one that finds no change)
    assert value >= 5
    assert value == pytest.approx(
        sum(sum(c.values()) for c in rec.update_chunks) / w.ticks)


def test_pipelined_cell_counts_interleaved_microbatches(stale_window):
    w, rec = stale_window
    value = cells.metric_reader(INTERLEAVED)(w)
    assert value == sum(m.between_chunks for m in rec.microbatches) / w.ticks
    assert 0 <= value <= len(rec.microbatches) / w.ticks
    # the stale answers are the interleaved microbatches' lanes (on the
    # CPU the tiny update may finish before the first Poisson arrival)
    assert (w.staleness > 0).sum() == sum(
        m.size for m in rec.microbatches if m.between_chunks)


def _window(ticks, answered, trace_=None):
    return harness.Window(
        ticks=ticks, answered=answered, latencies=np.full(answered, 0.5),
        staleness=np.zeros(answered, np.int32), updates=4 * ticks,
        live_edges=[10] * (ticks + 1), vertices=16, landmarks=2,
        microbatch=8, trace=trace_)


def _trace(programs):
    return devicetrace.DeviceTrace(window_s=10.0, chips=1, busy_s=8.0,
                                   programs=programs, ops={}, sweeps=[],
                                   gaps=[])


class _OldHost(NamedTuple):
    """A microbatch record of a program that does not mark interleaving."""
    size: int
    service_s: float
    waves: int | None
    live_lane_waves: int | None
    bit_packed: bool | None


def _publish(monkeypatch, update_chunks, marks, old_hosts=False):
    mbs = tuple(_OldHost(2, 0.1, 3, 4, True) if old_hosts
                else trace.MicrobatchHost(2, 0.1, 3, 4, True, m)
                for m in marks)
    kwargs = {} if update_chunks is None else {"update_chunks": update_chunks}
    monkeypatch.setattr(trace, "_last_run", trace.RunRecord(
        host_s=({"serve.prepare.fold": 0.1},) * 2, microbatches=mbs,
        construct_s={"serve.construct.load": 1.0}, **kwargs))


PIPELINED = ({"search-seed": 1, "search": 6, "repair-seed": 1,
              "repair": 4, "finish": 1},
             {"search-seed": 1, "search": 5, "repair-seed": 1,
              "repair": 3, "finish": 1})


def test_hand_made_record_values(monkeypatch):
    _publish(monkeypatch, PIPELINED, [True, True, False, True, False])
    tr = _trace({"jit_search_chunk": 6.0, "jit_repair_chunk": 3.0,
                 "jit_update_finish": 0.6, "jit_apply_batch": 0.4,
                 "jit_bounded_bibfs": 5.0})
    w = _window(2, 10, tr)
    assert cells.metric_reader(CHUNKS)(w) == pytest.approx(12.0)
    # update programs' 10 s over 24 dispatches; the BiBFS is not counted
    assert cells.metric_reader(CHUNK_S)(w) == pytest.approx(10.0 / 24)
    assert cells.metric_reader(INTERLEAVED)(w) == pytest.approx(1.5)


def test_hand_made_record_with_nothing_interleaved(monkeypatch):
    _publish(monkeypatch, PIPELINED, [False, False])
    assert cells.metric_reader(INTERLEAVED)(_window(2, 4)) == 0.0


SILENT = [(name, case) for name in READERS
          for case in ("sync", "no_counter", "other_ticks")] \
    + [(INTERLEAVED, "old_hosts")]


@pytest.mark.parametrize("name,case", SILENT)
def test_silent_where_nothing_was_counted(monkeypatch, name, case):
    """A sync run (no dispatch counted), a program without the dispatch
    counter, a record of another tick count, or microbatches without the
    mark give no value and no error."""
    chunks = {"sync": ({}, {}), "no_counter": None, "old_hosts": PIPELINED,
              "other_ticks": PIPELINED}[case]
    _publish(monkeypatch, chunks, [True, False],
             old_hosts=case == "old_hosts")
    ticks = 3 if case == "other_ticks" else 2
    w = _window(ticks, 4, _trace({"jit_search_chunk": 1.0}))
    assert cells.metric_reader(name)(w) is None


@pytest.mark.parametrize("case", NO_RECORD)
@pytest.mark.parametrize("name", READERS)
def test_silent_without_program_records(monkeypatch, name, case):
    """A program without its records, or the record of another run, gives
    no value and no error."""
    NO_RECORD[case](monkeypatch)
    w = _window(1, 2, _trace({"jit_search_chunk": 1.0}))
    assert cells.metric_reader(name)(w) is None


def test_chunk_s_needs_the_trace(monkeypatch):
    _publish(monkeypatch, PIPELINED, [True, False])
    assert cells.metric_reader(CHUNK_S)(_window(2, 4)) is None
