"""Benchmark harness — one module per paper table/figure (deliverable (d)).

  python -m benchmarks.run [--full] [--only speed,ppo,satisfaction,shift,roofline]

Prints ``name,us_per_call,derived`` CSV rows (assignment format).  --full uses
paper-scale training budgets; the default quick mode validates the same
claims with reduced budgets suited to this single-CPU container.

Every benchmark's results are also PERSISTED through the shared
observability sink (``repro.obs.write_benchmark_json``): ``BENCH_<name>.json``
is written to the repo root (schema_version, git sha, backend/device
provenance, CSV rows, plus whatever summary dict the module left in its
``LAST_SUMMARY`` global) so the perf trajectory survives the run — CI
uploads them as artifacts.  ``--metrics-out PATH`` additionally appends one
JSONL record per benchmark (same schema as ``rl_train --metrics-out``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODULES = {
    "speed": ("benchmarks.speed_table", "Table 2 / Fig 1: env + PPO throughput"),
    "ppo": ("benchmarks.ppo_shopping", "Fig 4a: PPO vs max-charge baseline"),
    "satisfaction": ("benchmarks.satisfaction_sweep", "Fig 4b/c: alpha sweep"),
    "shift": ("benchmarks.price_shift", "Fig 5: price-year distribution shift"),
    "fleet": ("benchmarks.fleet_throughput", "Fleet: heterogeneous stations, one vmap"),
    "fleet_sharded": (
        "benchmarks.fleet_sharded",
        "Fleet: station axis sharded over the device mesh",
    ),
    "v2g": (
        "benchmarks.v2g",
        "V2G: allow_v2g throughput + mixed-scenario PPO profit vs baselines",
    ),
    "grid": (
        "benchmarks.grid",
        "Grid: feeder-envelope allocate cost + grid_aware vs max-charge violations",
    ),
    "serve": (
        "benchmarks.serve",
        "Serve: batched-policy inference step, obs/sec + p50/p99 latency",
    ),
    "roofline": ("benchmarks.roofline_report", "dry-run + roofline tables"),
}


def persist(name: str, rows, summary: dict | None, quick: bool) -> str:
    """Write ``BENCH_<name>.json`` via the shared obs sink; return its path."""
    from repro.obs import write_benchmark_json

    return write_benchmark_json(name, rows, summary=summary, quick=quick)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--no-persist", action="store_true", help="skip writing BENCH_<name>.json"
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="append one JSONL record per benchmark (manifest + summary + "
        "rows) — the CI artifact sink",
    )
    ap.add_argument(
        "--list",
        action="store_true",
        help="print every registered benchmark (name: description) and exit",
    )
    args = ap.parse_args()

    if args.list:
        for name, (_, desc) in MODULES.items():
            print(f"{name}: {desc}")
        return

    names = list(MODULES) if args.only is None else args.only.split(",")
    unknown = [n for n in names if n not in MODULES]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; choose from {list(MODULES)}")
    from repro.utils import use_accurate_transcendentals, use_compile_cache

    use_accurate_transcendentals()
    use_compile_cache()
    writer = None
    if args.metrics_out:
        from repro.obs import MetricsWriter

        writer = MetricsWriter(args.metrics_out, run="benchmarks", quick=not args.full)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod_name, desc = MODULES[name]
        print(f"# --- {name}: {desc}", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            rows = mod.run(quick=not args.full)
            for rname, val, derived in rows:
                print(f"{rname},{val:.3f},{derived}", flush=True)
            summary = getattr(mod, "LAST_SUMMARY", None)
            if not args.no_persist:
                path = persist(name, rows, summary, not args.full)
                print(f"# wrote {os.path.relpath(path, REPO_ROOT)}", flush=True)
            if writer is not None:
                writer.write(
                    {
                        "benchmark": name,
                        "wall_s": round(time.perf_counter() - t0, 1),
                        **(summary or {}),
                        "rows": [
                            {"name": r, "us_per_call": round(float(v), 3), "derived": d}
                            for r, v, d in rows
                        ],
                    },
                    kind="benchmark",
                )
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},nan,FAILED: {type(e).__name__}: {e}", flush=True)
            if writer is not None:
                writer.write(
                    {"benchmark": name, "error": f"{type(e).__name__}: {e}"},
                    kind="benchmark_failure",
                )
        print(f"# {name} took {time.perf_counter()-t0:.0f}s", flush=True)
    if writer is not None:
        writer.close()
        print(f"# metrics JSONL: {writer.path}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
