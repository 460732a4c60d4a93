#!/usr/bin/env python3
"""Chargax benchmark: run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 bench/run.py --workload ppo.paper16_shop --seed 7 --seconds 30 --trace 0

A cell names a configuration (``bench/configs/<config>.json``, with its
tables' fingerprint beside it) and a traffic mix (``bench/traffic/<traffic>.json``,
whose ``driver`` picks the code in ``bench/harness/drivers.py``); the limits
that decide ``correct`` are in ``bench/limits/<workload>.json``, and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  All are found by
the names in ``BENCHMARK.json``, so a new cell, mix or metric is new files and
entries only.

A run builds and warms up the cell's program (set-up), calls it for
``--seconds`` (the window: nothing may compile there), reads the device
memory peak, frees the program and recomputes the first calls with the
plain reference in ``bench/reference``.  With ``--trace 1`` the window is
a few calls under the profiler, with the program's named scopes on, and
the result carries the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks`` last: each number compared beside its limit).  The
same checks are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    recorded_tables: dict
    rate_metric: str
    per_layer: list


def _read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``."""
    spec = _read_json(root, "BENCHMARK.json")
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {[w['name'] for w in spec['workloads']]}")
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = _read_json(root, conf["file"])
    tables_file = conf["file"][: -len(".json")] + ".tables.json"
    rates = [
        m["name"] for m in spec["end_to_end"]
        if m["name"] != "setup_s" and name in m.get("workloads", [name])
    ]
    if len(rates) != 1:
        raise SystemExit(f"{name}: expected one end-to-end rate besides setup_s, found {rates}")
    reported = {m["name"] for m in spec["end_to_end"] if name in m.get("workloads", [name])}
    per_layer = [
        m for m in spec["per_layer"]
        if name in m.get("workloads", [name] if m["moves"] in reported else [])
    ]
    return Cell(
        name=name,
        chips=int(wl["chips"]),
        config=config,
        traffic=_read_json(root, "bench", "traffic", wl["traffic"] + ".json"),
        limits=_read_json(root, "bench", "limits", name + ".json"),
        recorded_tables=_read_json(root, tables_file),
        rate_metric=rates[0],
        per_layer=per_layer,
    )


def load_reader(root: str, metric: str):
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def pin_one_chip() -> None:
    """Show this process one chip; must run before the TPU backend starts."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""

    trace: object
    n_calls: int
    env_steps: int
    flops_per_call: int
    env_step_bytes: int
    env_step_ops: int
    peak: dict


def timed_window(driver, seconds: float, first: int) -> tuple[int, int, float]:
    """Calls from ``first`` until ``seconds`` have passed; the call running
    then finishes and counts.  Returns (calls, env-steps, elapsed s)."""
    from repro.obs import compile_guard

    i, steps = first, 0
    with compile_guard("measured window"):
        t0 = time.perf_counter()
        while True:
            steps += driver.call(i)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        driver.finish()
        elapsed = time.perf_counter() - t0
    return i - first, steps, elapsed


def traced_window(driver, n_calls: int, first: int, log_dir: str, keep: str | None = None):
    """``n_calls`` calls under the profiler, inside a ``bench/window`` span."""
    import jax

    from bench.harness import trace
    from repro.obs import compile_guard

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with compile_guard("traced window"):
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                for i in range(first, first + n_calls):
                    with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
                        driver.call(i)
                driver.finish()
        finally:
            jax.profiler.stop_trace()
    ops, spans = trace.load_xplane(trace.find_xplane(log_dir), trace.scope_map(driver.hlo_text()))
    shutil.rmtree(log_dir, ignore_errors=True)
    if keep:
        trace.save_records(keep, ops, spans, max_ops=3000)
    return trace.TraceData(ops, spans)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def check_lines(checks: dict, limits: dict) -> list[str]:
    return [f"check {k} = {v!r} (limit {limits[k]!r})" for k, v in checks.items()]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="PATH", help="with --trace 1: also write the first device ops as a small recorded trace")
    args = ap.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    devices = start_jax(cell)
    return run_cell(cell, args, devices) if devices else 2


def start_jax(cell: Cell) -> list | None:
    """Start JAX for ``cell`` as ``rl_train`` does (one chip pinned where the
    cell takes one, accurate transcendentals, the persistent compile cache);
    the cell's TPU devices, or None where JAX finds fewer."""
    if cell.chips == 1:
        pin_one_chip()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu would log to a fixed /tmp path
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.utils import use_accurate_transcendentals, use_compile_cache

    use_accurate_transcendentals()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform!r} device(s). Nothing was measured.",
            file=sys.stderr,
        )
        return None
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices[: cell.chips]


def run_cell(cell: Cell, args, devices) -> int:
    """Set-up, window, reference check and result line, on ``devices``."""
    import jax

    from bench.harness import counts, peaks
    from bench.harness.drivers import DRIVERS, N_CHECK
    from repro import obs

    if args.trace:
        # the named scopes live only in op metadata, which the persistent
        # cache leaves out of its key: without this the annotated program
        # would load the plain one's executable, scopes and all
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        obs.enable_trace_annotations(True)
    t0 = time.perf_counter()
    driver = DRIVERS[cell.traffic["driver"]](cell.config, cell.traffic, args.seed, cell.recorded_tables, devices)
    t1 = time.perf_counter()
    driver.setup()
    setup_s = time.perf_counter() - T_START
    print(
        f"bench: set-up {setup_s:.2f} s: {t0 - T_START:.2f} s to start JAX and import, "
        f"{t1 - t0:.2f} s tables and program, {driver.compile_s:.2f} s trace, lower and compile, "
        f"{driver.warmup_s:.2f} s warm-up call | {driver.memory_text()}",
        file=sys.stderr,
    )

    first = 1  # call 0 is the warm-up
    if args.trace:
        n_calls = int(cell.traffic["trace_calls"])
        tr = traced_window(driver, n_calls, first, os.path.join(BENCH, "_out", "trace-" + cell.name), getattr(args, "keep_trace", None))
    else:
        n_calls, steps, elapsed = timed_window(driver, args.seconds, first)
    # the reference follows the first calls; make them where the window did not
    for i in range(first + n_calls, N_CHECK):
        driver.call(i)
    driver.finish()
    peak_bytes = memory_peak(devices)
    driver.release()
    attempted, failed = driver.attempted_failed(first, first + n_calls)

    if args.trace:
        # the reference carries no scopes: let it share the untraced runs'
        # cache entry instead of compiling again
        jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    checks = {**driver.table_numbers(), **driver.numbers(driver.kept, driver.reference())}
    checks["failed_calls"] = float(failed)
    missing = sorted(set(checks) ^ set(cell.limits))
    if missing:
        raise SystemExit(f"{cell.name}: checks and limits differ on {missing}")
    correct = attempted > 0 and all(checks[k] <= cell.limits[k] for k in checks)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        reading = Reading(
            trace=tr,
            n_calls=n_calls,
            env_steps=n_calls * driver.env_steps_per_call,
            flops_per_call=driver.flops_per_call,
            env_step_bytes=counts.env_step_bytes(cell.config, driver.tables),
            env_step_ops=counts.env_step_ops(cell.config, driver.tables),
            peak=peaks.peak(dev.device_kind),
        )
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(ROOT, m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_ns() / 1e9, window_s=tr.window_ns / 1e9)
        result.update(metrics=metrics, device=device, breakdown={"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()})
    else:
        metrics = {
            cell.rate_metric: {"value": steps / elapsed, "unit": "env-steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result.update(metrics=metrics, device=device)
    result["checks"] = {k: {"value": v, "limit": cell.limits[k]} for k, v in checks.items()}
    lines = check_lines(checks, cell.limits)
    print(f"bench: {cell.name} seed {args.seed}: {n_calls} calls in the window, correct={correct}", file=sys.stderr)
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
