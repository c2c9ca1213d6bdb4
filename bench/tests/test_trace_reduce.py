"""The reduction from trace events to busy time, program time, sweep
events and idle gaps, on hand-made events, and the algorithm's bytes per
sweep by hand."""
import pytest

from benchlib import devicetrace as dt

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(line, name, start, dur, plane=DEV):
    return dt.Event(plane, line, name, float(start), float(dur))


EVENTS = [
    # two programs; the first runs two sweeps (one overlapping op)
    ev(dt.MODULES_LINE, "jit_bounded_bibfs", 100, 400),
    ev(dt.OPS_LINE, "relax_sweep_pallas_kernel", 100, 150),
    ev(dt.OPS_LINE, "fusion.1", 200, 100),          # overlaps the first
    ev(dt.OPS_LINE, "relax_sweep_pallas_kernel", 350, 150),
    ev(dt.MODULES_LINE, "jit_batchhl_update", 800, 100),
    ev(dt.OPS_LINE, "relax_sweep_pallas_kernel", 800, 100),
    # host spans: a query microbatch, then tiling over the long gap
    ev("python", "bench.query_microbatch", 50, 500, plane=HOST),
    ev("python", "bench.prepare_tiling", 520, 260, plane=HOST),
    ev("python", "not.ours", 0, 10_000, plane=HOST),
]


def test_merge_union():
    assert dt.merge([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 7), (8, 9)]


def test_reduce_busy_programs_and_gaps():
    keep = [e for e in EVENTS if e.name != "not.ours"]
    tr = dt.reduce(keep, window_s=1e-6, window_ns=(0, 1000))
    # ops cover [100, 300), [350, 500) and [800, 900): 450 ns busy
    assert tr.busy_s == pytest.approx(450e-9)
    assert tr.idle_share == pytest.approx(0.55)
    assert tr.programs == {"jit_bounded_bibfs": pytest.approx(400e-9),
                           "jit_batchhl_update": pytest.approx(100e-9)}
    assert tr.program_seconds(dt.BIBFS_PROGRAM) == pytest.approx(400e-9)
    assert tr.program_seconds(dt.UPDATE_PROGRAM) == pytest.approx(100e-9)
    assert [s.program for s in tr.sweeps] == \
        ["jit_bounded_bibfs"] * 2 + ["jit_batchhl_update"]
    # gaps, longest first: [500, 800) mostly under tiling; [0, 100)
    # partly under the microbatch; [900, 1000) under no span of ours;
    # [300, 350) inside the microbatch
    assert tr.gaps == [
        ("host: bench.prepare_tiling", pytest.approx(300e-9)),
        ("host: bench.query_microbatch", pytest.approx(100e-9)),
        ("host: none", pytest.approx(100e-9)),
        ("host: bench.query_microbatch", pytest.approx(50e-9))]
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["program jit_bounded_bibfs",
                                   pytest.approx(400e-9)]
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


def test_busy_is_averaged_over_chips():
    two = [ev(dt.OPS_LINE, "a", 0, 100),
           ev(dt.OPS_LINE, "a", 0, 300, plane="/device:TPU:1")]
    tr = dt.reduce(two, window_s=400e-9, window_ns=(0, 400))
    assert tr.chips == 2 and tr.busy_s == pytest.approx(200e-9)


def test_sweep_bytes_by_hand():
    # 10 live directed edges and 4 vertices, 3 planes:
    # 3 * (12 * 10 + 8 * 4) = 3 * 152 = 456 bytes
    assert dt.sweep_bytes(10, 4, 3) == 456
    # the Youtube cell's BiBFS side: 32 planes, 2^20 vertices and
    # 6,291,456 live directed edges
    assert dt.sweep_bytes(6_291_456, 1 << 20, 32) == \
        32 * (12 * 6_291_456 + 8 * (1 << 20))
