"""One run of one cell: set-up, the measured window, the check.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The window drives the program's own serve loop (`repro.launch.serve.
ServeLoop`, built from a `ServeSpec`). Set-up runs the loop once for a
single tick: it generates the graph, tiles it, builds the labelling and
compiles every program the cell's ticks and microbatches use. Its tick
time sizes the measured loop to the whole number of ticks nearest to
`--seconds`. The measured loop builds the labelling again (set-up too);
its `on_start` hook opens the window, and the window closes when the loop
returns.

The check then rebuilds every version's graph from the recorded initial
edges and update batches, and compares with the plain reference
(`reference.py`): every committed labelling, every answer served in the
window at the version that served it, and the staleness the
configuration allows.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

from benchlib import cells as cells_mod
from benchlib import devicetrace, reference

#: Hop cap of the control's searches: answers come from the labelling's
#: Eq.-3 bound alone (see `control`).
CONTROL_MAX_STEPS = 0


class NoChipError(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class CompileClock:
    """Backend compile seconds and count, from JAX's own monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.count += 1


class StreamRecorder:
    """Stands in for `repro.graphs.generators` inside the serve module and
    keeps what the traffic generator hands the program: the initial edge
    list and each tick's update batch."""

    def __init__(self, gen):
        self._gen = gen
        self.initial: np.ndarray | None = None
        self.batches: list[list[tuple]] = []

    def __getattr__(self, name):
        return getattr(self._gen, name)

    def barabasi_albert(self, *args, **kwargs):
        edges = self._gen.barabasi_albert(*args, **kwargs)
        self.initial = np.array(edges, copy=True)
        return edges

    def random_batch_updates(self, *args, **kwargs):
        ups = self._gen.random_batch_updates(*args, **kwargs)
        self.batches.append([tuple(u) for u in ups])
        return ups


@contextlib.contextmanager
def recording(serve_mod):
    if not hasattr(serve_mod, "gen"):
        raise cells_mod.CellError(
            "repro.launch.serve no longer draws its stream through "
            "`gen`; the benchmark cannot record the inputs it checks")
    rec = StreamRecorder(serve_mod.gen)
    serve_mod.gen = rec
    try:
        yield rec
    finally:
        serve_mod.gen = rec._gen


@contextlib.contextmanager
def control(serve_mod):
    """The control: every query answered with its BiBFS capped at
    CONTROL_MAX_STEPS waves, i.e. from the labelling's upper bound alone."""
    orig = serve_mod.batched_query

    def capped(*args, **kwargs):
        return orig(*args, **{**kwargs, "max_steps": CONTROL_MAX_STEPS})
    serve_mod.batched_query = capped
    try:
        yield
    finally:
        serve_mod.batched_query = orig


@dataclasses.dataclass
class Window:
    """What the measured serve loop did, as the metric readers see it."""
    ticks: int
    answered: int
    latencies: np.ndarray       # seconds, due time -> answer
    staleness: np.ndarray       # versions behind the head, per answer
    updates: int                # edge updates committed
    live_edges: list[int]       # undirected live edges, per version
    vertices: int
    landmarks: int
    microbatch: int
    trace: "devicetrace.DeviceTrace | None" = None
    peaks: dict | None = None


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the control (answers from the labelling's "
                         "bound alone); its check must fail")
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChipError(
            f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int | None:
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    `.jax_cache/`, so that only a checkout's first run compiles."""
    import jax
    path = os.path.join(cells_mod.ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # No eviction: it reads an access-time file per entry and fails on an
    # entry written without one; a run's programs are few and fixed.
    jax.config.update("jax_compilation_cache_max_size", -1)


def window_ticks(seconds: float, tick_s: float, cfg: dict, traffic: dict,
                 initial_edges: int) -> int:
    """Whole ticks nearest to `seconds`, within the edge slots the
    configuration provisions."""
    want = max(1, round(seconds / tick_s))
    inserts = max(1, round(traffic["updates_per_tick"]
                           * traffic["scenario_params"]["ins_frac"]))
    room = (cfg["graph"]["capacity"] - initial_edges) // inserts
    if want > room:
        log(f"window: {want} ticks wanted, capacity holds {room}")
    return max(1, min(want, room))


def run_cell(args, t_start: float, *, cell: cells_mod.Cell | None = None,
             require_chip: bool = True) -> dict:
    """One run; returns the result line's object. Tests pass a `cell` of
    their own and `require_chip=False`."""
    cell = cell or cells_mod.load_cell(args.workload)
    cells_mod.check_scenario(cell.traffic)
    device = device_info(cell.chips, require_chip)
    peaks = devicetrace.peaks_for(device["kind"]) if require_chip else None
    enable_compile_cache()
    clock = CompileClock()

    from repro.launch import serve as serve_mod

    cfg, traffic = cell.config, cell.traffic
    stack = contextlib.ExitStack()
    with stack:
        if args.control:
            stack.enter_context(control(serve_mod))
        # Warm-up: one tick of the cell's own shapes.
        spec = cells_mod.serve_spec(cfg, traffic, batches=1, seed=args.seed)
        warm = serve_mod.ServeLoop(spec.to_serve_config())
        marks: dict[str, float] = {}

        def warm_start(snap):
            marks["start"], marks["compile_s"] = time.time(), clock.seconds
        warm.on_start = warm_start
        with recording(serve_mod) as rec:
            warm.run()
        # The tick's time less what it spent compiling: a cold first run
        # sizes its window like a warm one.
        tick_s = time.time() - marks["start"] \
            - (clock.seconds - marks["compile_s"])
        initial_edges = len(rec.initial)
        if initial_edges != cfg["edges"]:
            raise cells_mod.CellError(
                f"the program generated {initial_edges} edges for "
                f"{cfg['name']!r}, whose file records {cfg['edges']}")
        del warm
        ticks = window_ticks(args.seconds, tick_s, cfg, traffic,
                             initial_edges)
        log(f"warm-up tick {tick_s:.3f}s; window of {ticks} tick(s)")

        spec = cells_mod.serve_spec(cfg, traffic, batches=ticks,
                                    seed=args.seed)
        loop = serve_mod.ServeLoop(spec.to_serve_config())
        labellings: dict[int, object] = {}
        tracer = devicetrace.Tracer(cells_mod.ROOT) if args.trace else None

        def on_start(snap):
            labellings[snap.version] = snap.labelling
            marks["window"] = time.time()
            marks["compiles"] = clock.count
            if tracer:
                tracer.start()

        def on_commit(tick, snap):
            labellings[snap.version] = snap.labelling

        loop.on_start, loop.on_commit = on_start, on_commit
        with recording(serve_mod) as rec:
            if tracer:
                stack.enter_context(tracer.host_spans(serve_mod, loop))
            report = loop.run()
            t_end = time.time()
            if tracer:
                tracer.stop()
    window_s = t_end - marks["window"]
    compiles = clock.count - marks["compiles"]
    setup_s = marks["window"] - t_start
    log(f"window: {window_s:.3f}s, {ticks} tick(s), {compiles} compile(s) "
        f"inside it; set-up {setup_s:.3f}s")
    device["memory_peak_bytes"] = memory_peak(cell.chips)

    # Move what the check needs to the host, then free the device state.
    records = [(m.version, m.staleness, np.asarray(m.qs), np.asarray(m.qt),
                np.asarray(m.answers)) for m in report.microbatches]
    latencies = report.latencies()
    staleness = report.staleness()
    labs = {v: tuple(np.asarray(x) for x in
                     (lab.landmarks, lab.dist, lab.hub, lab.highway))
            for v, lab in labellings.items()}
    committed = len(report.ticks)
    del loop, report, labellings

    t_check = time.time()
    checks, live = check(cfg, traffic, rec, labs, records, committed)
    log(f"check against the reference: {time.time() - t_check:.3f}s")
    window = Window(
        ticks=committed, answered=int(latencies.size),
        latencies=latencies, staleness=staleness,
        updates=sum(len(b) for b in rec.batches[:committed]),
        live_edges=live, vertices=cfg["vertices"],
        landmarks=cfg["landmarks"],
        microbatch=cfg["serving"]["microbatch"], peaks=peaks)

    if tracer:
        window.trace = tracer.reduce()
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            value = cells_mod.metric_reader(m["name"])(window)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s,
                  "queries_per_s": window.answered / window_s,
                  "query_p90_s": (float(np.percentile(latencies, 90))
                                  if latencies.size else None),
                  "updates_per_s": window.updates / window_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if values.get(m["name"]) is not None}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": int(ticks * traffic["queries_per_tick"]),
              "failed": int(ticks * traffic["queries_per_tick"]
                            - window.answered),
              "metrics": metrics, "device": device}
    if tracer:
        result["breakdown"] = window.trace.breakdown()
    result["checks"] = checks
    return result


def unreachable(x: np.ndarray, inf: int) -> np.ndarray:
    """The program's distances with its no-path values as the reference's."""
    x = np.asarray(x).astype(np.int64)
    return np.where(x >= inf, reference.UNREACHABLE, x)


def check(cfg: dict, traffic: dict, rec: StreamRecorder, labs: dict,
          records: list, committed: int) -> tuple[dict, list[int]]:
    """Compare the window's output with the plain reference.

    Returns ({name: {"value", "limit"}}, live undirected edges per
    version). Each value is a count of faults, held to its limit."""
    n = cfg["vertices"]
    inf = cfg["unreachable_at_least"]
    observed = rec.initial is not None and len(rec.batches) >= committed
    checks = {"stream_unobserved": {"value": int(not observed), "limit": 0}}
    if not observed:
        return checks, []
    edges = reference.EdgeSet(n, rec.initial)
    landmarks = labs[0][0]
    deg = edges.degrees()
    # Landmarks: the R highest degrees of the initial graph (ties free).
    top = np.sort(deg)[::-1][:cfg["landmarks"]]
    bad_landmarks = int(len(set(landmarks.tolist())) != len(landmarks)
                        or not np.array_equal(np.sort(deg[landmarks])[::-1],
                                              top))
    by_version: dict[int, list] = {}
    for rec_v in records:
        by_version.setdefault(rec_v[0], []).append(rec_v)

    wrong_labels = wrong_answers = 0
    live = []
    for version in range(committed + 1):
        if version:
            edges.apply(rec.batches[version - 1])
        live.append(len(edges.keys))
        csr = edges.csr()
        if version not in labs:
            wrong_labels += cfg["landmarks"] * n
        else:
            _, dist, hub, highway = labs[version]
            want_d, want_h = reference.landmark_planes(csr, landmarks)
            wrong_labels += int(np.sum(unreachable(dist, inf) != want_d))
            wrong_labels += int(np.sum(hub != want_h))
            wrong_labels += int(np.sum(unreachable(highway, inf)
                                       != want_d[:, landmarks]))
        mine = by_version.pop(version, [])
        if mine:
            qs = np.concatenate([m[2] for m in mine])
            qt = np.concatenate([m[3] for m in mine])
            got = unreachable(np.concatenate([m[4] for m in mine]), inf)
            want = reference.pair_distances(csr, qs, qt)
            wrong_answers += int(np.sum(got != want))
    # Answers at a version the window never committed are wrong.
    wrong_answers += sum(len(m[2]) for v in by_version.values() for m in v)
    served = sum(len(m[2]) for m in records)
    due = committed * traffic["queries_per_tick"]
    stale = max((m[1] for m in records), default=0)
    checks.update({
        "wrong_answers": {"value": wrong_answers, "limit": 0},
        "unanswered": {"value": due - served, "limit": 0},
        "wrong_label_entries": {"value": wrong_labels, "limit": 0},
        "wrong_landmarks": {"value": bad_landmarks, "limit": 0},
        "max_staleness": {"value": int(stale),
                          "limit": cfg["guarantee"]["max_staleness"]},
    })
    return checks, live


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.time() if t_start is None else t_start
    args = parse_args(argv)
    try:
        result = run_cell(args, t_start)
    except NoChipError as e:
        log(f"bench: {e}")
        return 1
    except cells_mod.CellError as e:
        log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
