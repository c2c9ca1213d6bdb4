"""From a profiler trace to device busy time, program and kernel time.

`Tracer` records the window with JAX's profiler and marks the host work
of the serve loop with `TraceAnnotation` spans named `bench.*`, taken
from the benchmark's side: each wraps a call the loop makes, and none
changes what it does. `load_events` reads the `.xplane.pb` into plain
`Event` rows, and `reduce` turns them into a `DeviceTrace`. Both work on
rows, so the tests feed them a small recorded trace.

`sweep_bytes` is the algorithm's HBM traffic of one relaxation sweep:
per plane, each live directed edge reads its source key, destination
and validity (12 B), and each vertex's key is read and its candidate
written (8 B). Padded tile slots and the kernel's compare work are not
counted, so a share of the roofline reads the same work whatever
implements the sweep.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import json
import os
import re
import shutil
import time

#: Device planes in the xplane (one per chip).
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: Line of a device plane with one event per executed operation.
OPS_LINE = "XLA Ops"
#: Line of a device plane with one event per executed program.
MODULES_LINE = "XLA Modules"
#: Prefix of the benchmark's own host spans.
SPAN_PREFIX = "bench."
#: The relaxation sweep's Pallas kernel, by its operation's name.
SWEEP_KERNEL = re.compile(r"relax_sweep_pallas")
#: Programs that answer queries by BiBFS.
BIBFS_PROGRAM = re.compile(r"bounded_bibfs")
#: Programs of the update path: apply, search, repair, commit.
UPDATE_PROGRAM = re.compile(
    r"batchhl_update|apply_batch|batch_requirements|search_|repair_|"
    r"update_finish")

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class UnknownDeviceError(KeyError):
    """The peaks table has no row for this device kind."""


def peaks_for(kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as fh:
        table = json.load(fh)
    try:
        return table["devices"][kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no peak rates for device kind {kind!r} in bench/peaks.json; "
            f"known: {sorted(table['devices'])}") from None


def sweep_bytes(live_directed_edges: float, vertices: int,
                planes: int) -> float:
    return planes * (12.0 * live_directed_edges + 8.0 * vertices)


def sweep_roofline(run) -> float | None:
    """The sweep kernel's share of its HBM roofline in a window, in %:
    the algorithm's bytes of every sweep the kernel ran (the window's
    mean live edges; the query microbatch's planes inside the BiBFS
    program, the landmark planes elsewhere) at the chip's peak HBM
    bandwidth, over the kernel's device time."""
    if run.trace is None or not run.trace.sweeps or run.peaks is None \
            or not run.live_edges:
        return None
    edges = 2.0 * sum(run.live_edges) / len(run.live_edges)
    moved = sum(sweep_bytes(edges, run.vertices,
                            run.microbatch if BIBFS_PROGRAM.search(ev.program)
                            else run.landmarks)
                for ev in run.trace.sweeps)
    kernel_s = sum(ev.dur_ns for ev in run.trace.sweeps) / 1e9
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / kernel_s


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    #: the program an operation ran in (ops line only; "" if unknown)
    program: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def short_name(line: str, name: str) -> str:
    """An operation as its HLO name (`%fusion.12`, not the instruction's
    whole text); a program without its fingerprint suffix."""
    if line == OPS_LINE:
        return name.split(" = ", 1)[0]
    return re.sub(r"\(\d+\)$", "", name)


def load_events(path: str) -> list[Event]:
    """Device operations and programs, and the `bench.*` host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                for ev in line.events:
                    out.append(Event(plane.name, line.name,
                                     short_name(line.name, ev.name),
                                     ev.start_ns, ev.duration_ns))
            elif not device:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.append(Event(plane.name, line.name, ev.name,
                                         ev.start_ns, ev.duration_ns))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _qualified(op: Event) -> str:
    return f"{op.program}/{op.name}" if op.program else op.name


def self_seconds(ops: list[Event]) -> dict[str, float]:
    """Device seconds per operation (`program/op` where its program is
    known), less the time of the operations nested inside it (a `while`
    holds its body's operations)."""
    out: dict[str, float] = collections.defaultdict(float)
    for chip in {o.plane for o in ops}:
        stack: list[Event] = []
        for o in sorted((o for o in ops if o.plane == chip),
                        key=lambda o: (o.start_ns, -o.dur_ns)):
            while stack and stack[-1].end_ns <= o.start_ns:
                stack.pop()
            if stack:
                out[_qualified(stack[-1])] -= \
                    min(o.end_ns, stack[-1].end_ns) - o.start_ns
            out[_qualified(o)] += o.dur_ns
            stack.append(o)
    return {k: v / 1e9 for k, v in out.items()}


def _attribute(ops: list[Event], programs: list[Event]) -> list[Event]:
    """Name each operation's program by the program event that holds it."""
    progs = sorted(programs, key=lambda e: e.start_ns)
    starts = [p.start_ns for p in progs]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        name = progs[i].name if i >= 0 and op.start_ns < progs[i].end_ns \
            else ""
        out.append(dataclasses.replace(op, program=name))
    return out


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    chips: int
    busy_s: float                       # mean over chips
    programs: dict[str, float]          # program -> device seconds
    ops: dict[str, float]               # operation -> device self seconds
    sweeps: list[Event]                 # sweep kernel events
    gaps: list[tuple[str, float]]       # longest idle gaps by host span

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def program_seconds(self, pattern: re.Pattern) -> float:
        return sum(s for name, s in self.programs.items()
                   if pattern.search(name))

    def breakdown(self) -> dict:
        top = sorted(self.programs.items(), key=lambda kv: -kv[1])[:5]
        top_ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:5]
        return {"device_ops": [[f"program {n}", s] for n, s in top]
                + [[f"op {n}", s] for n, s in top_ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def reduce(events: list[Event], window_s: float,
           window_ns: tuple[float, float] | None = None) -> DeviceTrace:
    """Busy union per chip, time per program and operation, sweep kernel
    events, and the ten longest idle gaps named by the host span that
    overlaps each most ("host: none" where no span does).

    `window_ns` clips to the traced window on the trace's clock; by
    default it spans the first to the last device event."""
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)]
    ops = [e for e in dev if e.line == OPS_LINE]
    mods = [e for e in dev if e.line == MODULES_LINE]
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    chips = sorted({e.plane for e in ops})
    if window_ns is None and ops:
        window_ns = (min(e.start_ns for e in ops),
                     max(e.end_ns for e in ops))
    busy = []
    gaps: list[tuple[float, float]] = []
    for chip in chips:
        iv = merge((max(e.start_ns, window_ns[0]),
                    min(e.end_ns, window_ns[1]))
                   for e in ops if e.plane == chip
                   and e.end_ns > window_ns[0] and e.start_ns < window_ns[1])
        busy.append(sum(e - s for s, e in iv))
        edges = [window_ns[0]] + [x for s, e in iv for x in (s, e)] \
            + [window_ns[1]]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    programs: dict[str, float] = collections.defaultdict(float)
    for m in mods:
        programs[m.name] += m.dur_ns / 1e9
    ops = _attribute(ops, mods)
    per_op = self_seconds(ops)
    sweeps = [o for o in ops if SWEEP_KERNEL.search(o.name)]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, cover = "host: none", 0.0
        for sp in spans:
            c = min(e, sp.end_ns) - max(s, sp.start_ns)
            if c > cover:
                best, cover = "host: " + sp.name, c
        named.append((best, (e - s) / 1e9))
    return DeviceTrace(
        window_s=window_s, chips=max(1, len(chips)),
        busy_s=sum(busy) / 1e9 / max(1, len(chips)),
        programs=dict(programs), ops=per_op, sweeps=sweeps,
        gaps=named)


class Tracer:
    """JAX's profiler over the window, written inside the checkout."""

    def __init__(self, root: str):
        self.dir = os.path.join(root, ".bench_trace")
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.time()

    def stop(self) -> None:
        import jax
        self.t1 = time.time()
        jax.profiler.stop_trace()

    def reduce(self) -> DeviceTrace:
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        events = load_events(paths[0])
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduce(events, self.t1 - self.t0)

    @contextlib.contextmanager
    def host_spans(self, serve_mod, loop):
        """Name the serve loop's host calls in the trace."""
        import jax

        def span(fn, name):
            def wrapped(*args, **kwargs):
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                    return fn(*args, **kwargs)
            return wrapped

        def chunks(fn, name):
            def wrapped(*args, **kwargs):
                gen = fn(*args, **kwargs)
                while True:
                    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                        try:
                            tag = next(gen)
                        except StopIteration as stop:
                            return stop.value
                    yield tag
            return wrapped

        targets = [(serve_mod, "batched_query", span, "query_microbatch"),
                   (serve_mod, "batchhl_update", span, "update"),
                   (serve_mod, "pipelined_update", chunks, "update_chunk"),
                   (serve_mod, "apply_batch", span, "apply_batch"),
                   (serve_mod, "make_batch", span, "make_batch"),
                   (serve_mod.gen, "random_batch_updates", span,
                    "draw_updates"),
                   (loop.engine, "prepare", span, "prepare_tiling")]
        saved = []
        for obj, attr, wrap, name in targets:
            if hasattr(obj, attr):
                saved.append((obj, attr, obj.__dict__.get(attr)))
                setattr(obj, attr, wrap(getattr(obj, attr), name))
        try:
            yield
        finally:
            for obj, attr, old in reversed(saved):
                if old is None:
                    delattr(obj, attr)
                else:
                    setattr(obj, attr, old)
