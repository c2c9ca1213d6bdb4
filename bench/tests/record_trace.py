"""Record the small TPU trace that `test_recorded_trace.py` reads.

    python bench/tests/record_trace.py [OUT_DIR]   # on one TPU chip

Serves one tick of a tiny cell (a 2048-vertex BA graph, R = 8, pallas
sweeps, microbatches of 8) through the harness's own tracer and host
spans, then writes the events `devicetrace.load_events` reads from the
trace to `data/tiny_tpu.events.json` (as rows: plane, line, name, start
and duration in ns) and what the reduction read from them to
`data/tiny_tpu.expected.json` (or both to OUT_DIR).
"""
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(out: str) -> int:
    import jax

    from benchlib import cells, devicetrace
    from repro.launch import serve

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    cfg = {"vertices": 2048, "landmarks": 8,
           "graph": {"family": "ba", "attach": 3, "capacity": 8192},
           "engine": {"backend": "pallas", "use_minplus_kernel": True,
                      "block_v": 512, "mesh": "none"},
           "serving": {"pipeline": False, "chunk_sweeps": 1,
                       "microbatch": 8}}
    with open(os.path.join(BENCH, "traffic", "reads-sat.json")) as fh:
        mix = dict(json.load(fh), queries_per_tick=16)
    tracer = devicetrace.Tracer(os.path.join(HERE, "data"))
    for traced in (False, True):   # the first run compiles
        loop = serve.ServeLoop(cells.serve_spec(
            cfg, mix, batches=1, seed=5).to_serve_config())
        if traced:
            loop.on_start = lambda snap: tracer.start()
            with tracer.host_spans(serve, loop):
                loop.run()
            tracer.stop()
        else:
            loop.run()
    path, = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                      recursive=True)
    events = devicetrace.load_events(path)
    with open(os.path.join(out, "tiny_tpu.events.json"), "w") as fh:
        json.dump([[e.plane, e.line, e.name, e.start_ns, e.dur_ns]
                   for e in events], fh)
    tr = tracer.reduce()
    expected = {"chips": tr.chips, "busy_s": tr.busy_s,
                "programs": sorted(tr.programs),
                "sweeps": len(tr.sweeps),
                "sweep_programs": sorted({s.program for s in tr.sweeps}),
                "spans": sorted({e.name for e in events
                                 if not devicetrace.DEVICE_PLANE.match(
                                     e.plane)})}
    with open(os.path.join(out, "tiny_tpu.expected.json"),
              "w") as fh:
        json.dump(expected, fh, indent=1)
    print(json.dumps(expected, indent=1))
    return 0


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    os.makedirs(out, exist_ok=True)
    sys.exit(main(out))
