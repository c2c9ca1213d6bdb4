"""The harness on the CPU at a size a test run holds: the tiny
configuration `data/tiny-fresh.json` (jnp sweeps) under a real mix."""
import json
import os
import time

from benchlib import cells, harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(BENCH, "tests", "data", "tiny-fresh.json")


def tiny_cell(traffic="reads-sat", **config):
    """A cell of `BENCHMARK.json`'s shape over the tiny configuration."""
    with open(TINY) as fh:
        cfg = json.load(fh)
    for key, value in config.items():
        cfg[key] = dict(cfg[key], **value) if isinstance(value, dict) \
            else value
    bench = cells.load_benchmark()
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as fh:
        mix = json.load(fh)
    return cells.Cell(name="tiny." + traffic, chips=1, config=cfg,
                      traffic=mix, end_to_end=tuple(bench["end_to_end"]),
                      per_layer=())


def run_tiny(cell, *flags, seed=2 ** 31 + 11):
    args = harness.parse_args(["--workload", cell.name, "--seed", str(seed),
                               "--seconds", "0.5", "--trace", "0", *flags])
    return harness.run_cell(args, time.time(), cell=cell, require_chip=False)
