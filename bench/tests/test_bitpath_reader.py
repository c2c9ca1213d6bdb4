"""The reader of `bibfs_bitpath_share.sat`, the share of answered
microbatches whose BiBFS ran the bit-packed unit-weight path: 100% after
a tiny unit-weight cell run on the CPU, the packed share of a record's
microbatches, and nothing where the program keeps no record of the
window or does not record the path."""
from typing import NamedTuple

import numpy as np
import pytest

from benchlib import cells, harness
from repro.launch import trace
from test_layer_readers import NO_RECORD, window  # noqa: F401 (fixture)

NAME = "bibfs_bitpath_share.sat"


def test_bitpath_share_in_range(window):  # noqa: F811
    w, _ = window
    # the tiny configuration's graph has unit weights
    assert cells.metric_reader(NAME)(w) == 100.0


@pytest.mark.parametrize("case", NO_RECORD)
def test_bitpath_share_silent_without_program_records(monkeypatch, case):
    """A program without the counters, or a record that is not the
    window's, gives no value and no error."""
    NO_RECORD[case](monkeypatch)
    assert cells.metric_reader(NAME)(_window(2)) is None


def _window(answered):
    return harness.Window(
        ticks=1, answered=answered, latencies=np.full(answered, 0.5),
        staleness=np.zeros(answered, np.int32), updates=4,
        live_edges=[10, 10], vertices=16, landmarks=2, microbatch=8)


class _OldHost(NamedTuple):
    """A microbatch record of a program that does not record the path."""
    size: int
    service_s: float
    waves: int | None
    live_lane_waves: int | None


@pytest.mark.parametrize("paths,want", [
    ((True, True, True), 100.0),
    ((True, False, True, False), 50.0),
    ((None, None), None),
], ids=["all_packed", "mixed", "no_field"])
def test_bitpath_share_counts_packed_microbatches(monkeypatch, paths, want):
    """The share of microbatches on the bit-packed path; a record whose
    microbatches lack the field gives nothing."""
    mbs = tuple(trace.MicrobatchHost(2, 0.1, 3, 4, p) if p is not None
                else _OldHost(2, 0.1, 3, 4) for p in paths)
    monkeypatch.setattr(trace, "_last_run", trace.RunRecord(
        host_s=({"serve.prepare.fold": 0.1},), microbatches=mbs,
        construct_s={"serve.construct.load": 1.0}))
    assert cells.metric_reader(NAME)(_window(2 * len(mbs))) == want
