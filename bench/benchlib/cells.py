"""Cells, configurations and traffic mixes, found by name.

`BENCHMARK.json` names each cell's configuration and traffic mix. The
configuration is the file its entry names (`configs/<config>.json`), the
mix `traffic/<mix>.json`, and each per-layer metric is read by
`metrics/<metric>.py`. Adding a
cell, a mix or a metric adds files; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(ValueError):
    """A cell, its files or the program's registry do not agree."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _load_json(path: str) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise CellError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _reports(metric: dict, cell: str, all_cells: list[str]) -> bool:
    return cell in metric.get("workloads", all_cells)


def load_cell(name: str, bench: dict | None = None) -> Cell:
    """The cell `name` of `bench` (default: BENCHMARK.json) with its
    files read."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    everyone = list(cells)
    e2e = tuple(m for m in bench["end_to_end"]
                if _reports(m, name, everyone))
    moved = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if m["moves"] in moved and _reports(m, name, everyone))
    for m in layer:
        metric_path(m["name"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def metric_path(metric: str) -> str:
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise CellError(f"no reader {os.path.relpath(path, ROOT)} for the "
                        f"per-layer metric {metric!r}")
    return path


def metric_reader(metric: str):
    """The `read(run)` function of `metrics/<metric>.py`."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
        metric_path(metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def check_scenario(traffic: dict) -> None:
    """Refuse to run when the program's scenario registry no longer has
    the parameters this mix was recorded with."""
    from repro.data.scenarios import get_scenario

    have = {k: v for k, v in
            dataclasses.asdict(get_scenario(traffic["scenario"])).items()
            if k not in ("name", "description")}
    want = traffic["scenario_params"]
    if have != want:
        diff = sorted(k for k in set(have) | set(want)
                      if have.get(k) != want.get(k))
        raise CellError(
            f"scenario {traffic['scenario']!r} in the program's registry "
            f"differs from traffic/{traffic['name']}.json on {diff}: "
            f"registry {have}, recorded {want}")


def serve_spec(config: dict, traffic: dict, *, batches: int, seed: int):
    """The program's `ServeSpec` for one serve-loop run of the cell."""
    from repro.launch.config import (EngineSpec, GraphSpec, ServeSpec,
                                     StreamSpec)

    graph, engine, serving = config["graph"], config["engine"], \
        config["serving"]
    return ServeSpec(
        graph=GraphSpec(n=config["vertices"], deg=graph["attach"],
                        graph=graph["family"], landmarks=config["landmarks"],
                        capacity=graph["capacity"]),
        engine=EngineSpec(backend=engine["backend"],
                          block_v=engine["block_v"],
                          use_minplus_kernel=engine["use_minplus_kernel"],
                          mesh=engine["mesh"]),
        stream=StreamSpec(batches=batches,
                          batch_size=traffic["updates_per_tick"],
                          scenario=traffic["scenario"],
                          queries=traffic["queries_per_tick"],
                          qps=float(traffic["arrival_qps"]),
                          microbatch=serving["microbatch"],
                          pipeline=serving["pipeline"],
                          chunk_sweeps=serving["chunk_sweeps"],
                          seed=seed, quiet=True))
