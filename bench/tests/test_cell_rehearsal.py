"""CPU rehearsal of the harness: the cells resolve, the scenario guard
holds, a tiny cell runs end to end and passes its check, and the
measurement path refuses a device that is not a TPU."""
import dataclasses
import json

import pytest

from benchlib import cells, harness
from tinycell import run_tiny, tiny_cell

BENCH = cells.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = cells.load_cell(name)
    cells.check_scenario(cell.traffic)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))
    spec = cells.serve_spec(cell.config, cell.traffic, batches=3, seed=1)
    assert spec.graph.n == cell.config["vertices"]
    assert spec.stream.batch_size == cell.traffic["updates_per_tick"]


@pytest.mark.parametrize("key,value", [("ins_frac", 0.9),
                                       ("query_skew", 1.2)])
def test_scenario_guard_trips(key, value):
    mix = cells.load_cell(BENCH["workloads"][0]["name"]).traffic
    moved = dict(mix, scenario_params=dict(mix["scenario_params"],
                                           **{key: value}))
    with pytest.raises(cells.CellError, match=key):
        cells.check_scenario(moved)


def test_unknown_workload_is_refused():
    with pytest.raises(cells.CellError, match="unknown workload"):
        cells.load_cell("no-such.cell")


def test_cpu_is_refused(capsys):
    """The measurement path exits non-zero and prints no result line."""
    rc = harness.main(["--workload", BENCH["workloads"][0]["name"],
                       "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "TPU" in out.err


def test_tiny_cell_end_to_end(no_disk_cache):
    bench = dict(BENCH, configs=[{"name": "tiny-fresh",
                                  "file": "bench/tests/data/tiny-fresh.json"}],
                 workloads=[{"name": "tiny.reads-sat", "config": "tiny-fresh",
                             "traffic": "reads-sat", "chips": 1}])
    cell = dataclasses.replace(cells.load_cell("tiny.reads-sat",
                                               bench=bench), per_layer=())
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "checks"
    json.dumps(res)


def test_tiny_stale1_cell_end_to_end(no_disk_cache):
    cell = tiny_cell("churn", serving={"pipeline": True},
                     guarantee={"max_staleness": 1})
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_staleness"]["value"] <= 1
