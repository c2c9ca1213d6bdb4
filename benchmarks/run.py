"""Benchmark harness: one module per paper table/figure + the tick trajectory.

Prints ``name,us_per_call,derived`` CSV rows. Modules:
  table3_update_time   — Table 3 (BHL⁺/BHL/BHLˢ/UHL⁺ update time)
  table4_construction  — Table 4 (construction, query time, label size)
  table5_affected      — Table 5 + Fig. 2 (affected-vertex counts)
  table6_directed      — Table 6 (directed graphs, two-plane BatchHL)
  fig6_batch_sizes     — Fig. 6 (amortized total time vs batch size)
  fig7_landmarks       — Figs. 7/8 (update/query time vs landmarks)
  ticks                — serving-tick latency per backend × mesh, plus
                         the serve-loop trajectory (open-loop query
                         p50/p95/p99 + staleness, sync vs pipeline)

``--fast`` trims datasets for CI-ish runs; default runs everything.
``--preset quick`` runs only the `ticks` module at CI size — the bench
CI job's configuration. ``--json PATH`` additionally persists every
emitted row in the bench-trajectory format (schema ``repro-bench/v1``:
``{"schema", "jax", "platform", "device_kind", "device_count", "rows":
[{name, us_per_call, derived}]}``) consumed by `benchmarks/compare.py`.

The harness measures the TPU: it exits non-zero when JAX finds none, so a
CPU timing is never recorded as a benchmark number.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _rows_to_json(rows: list[str]) -> list[dict]:
    out = []
    for row in rows:
        name, us, derived = row.split(",", 2)
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived})
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    ap.add_argument("--preset", default=None, choices=("quick",),
                    help="quick = the CI bench job: ticks module only, "
                         "small dataset, both backends, both meshes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all emitted rows as bench-trajectory JSON")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"benchmarks.run measures the TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")

    from benchmarks import (table3_update_time, table4_construction,
                            table5_affected, table6_directed,
                            fig6_batch_sizes, fig7_landmarks, ticks)
    modules = {
        "table3": table3_update_time,
        "table4": table4_construction,
        "table5": table5_affected,
        "table6": table6_directed,
        "fig6": fig6_batch_sizes,
        "fig7": fig7_landmarks,
        "ticks": ticks,
    }
    if args.preset and args.only:
        ap.error("--preset and --only are mutually exclusive")
    if args.preset == "quick":
        picked = ["ticks"]
    else:
        picked = (args.only.split(",") if args.only else list(modules))
    print("name,us_per_call,derived")
    t0 = time.time()
    all_rows: list[str] = []
    for name in picked:
        mod = modules[name]
        try:
            if name == "ticks" and (args.preset == "quick" or args.fast):
                # 6 ticks → 4 steady-state samples behind the 2 warmup
                # (compile + reshard-retrace) ticks the median drops.
                out = mod.run(datasets=("ba_2k",), ticks=6, batch_size=64,
                              queries=128)
            elif args.fast and name in ("table3", "table4"):
                out = mod.run(datasets=("ba_2k",))
            else:
                out = mod.run()
            all_rows += out
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
    print(f"# {len(all_rows)} rows in {time.time() - t0:.1f}s",
          file=sys.stderr)
    if args.json:
        payload = {"schema": "repro-bench/v1", "jax": jax.__version__,
                   "platform": dev.platform, "device_kind": dev.device_kind,
                   "device_count": len(jax.devices()),
                   "rows": _rows_to_json(all_rows)}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
