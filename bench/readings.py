#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/`` are set from.

    python3 bench/readings.py --workload ppo.paper16_shop --seeds 1,2,3 --program --control
    python3 bench/readings.py --workload sim.paper16_shop --seeds 1,2,3 --fault half_batch,altered_answer

For each seed, in one process, on the cell's own sizes and chips:
``--program`` runs the cell's first calls and compares them with the
reference, as a run does (the lower readings); ``--control`` puts the
reference, computed one precision lower (bfloat16), in the program's place
(the upper readings); ``--fault`` plants each named fault of
``bench/harness/faults.py`` under a program of its own.  Every mode of a
seed is compared with one computation of the reference.  Prints one JSON
line per seed and mode, then the largest and the least reading of each
number per mode.  No window is measured, so nothing here is a timing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from bench import run  # noqa: E402


def program_numbers(driver, ref_of) -> dict:
    import jax

    from bench.harness.drivers import N_CHECK

    for i in range(N_CHECK):
        driver.call(i)
    driver.finish()
    driver.kept = jax.device_get(driver.kept)
    return driver.numbers(driver.kept, ref_of)


def control_numbers(driver, ref_of) -> dict:
    import jax.numpy as jnp

    from bench.harness.drivers import N_CHECK

    low = driver.reference(jnp.bfloat16)
    got = {i: driver.as_output(low(i)) for i in range(N_CHECK)}
    return driver.numbers(got, ref_of)


def readings(cell, seeds: list[int], program: bool, control: bool, faults=(), out=print, devices=None) -> dict:
    """{mode: {number: largest reading over the seeds}}; one line per seed
    and mode to ``out``.  The reference of every seed is computed first;
    then each program is built, read on every seed and freed, so that one
    program at a time holds the chips."""
    import contextlib
    import gc

    from bench.harness.drivers import DRIVERS, N_CHECK
    from bench.harness.faults import FAULTS

    kind = cell.traffic["driver"]
    worst: dict = {}
    least: dict = {}  # a control's or fault's least reading is its upper one

    def build(fault=None):
        with FAULTS[fault](kind) if fault else contextlib.nullcontext():
            driver = DRIVERS[kind](cell.config, cell.traffic, seeds[0], cell.recorded_tables, devices)
            if fault:
                driver.setup()
        return driver

    def record(mode, seed, nums):
        out(json.dumps({"workload": cell.name, "seed": seed, "mode": mode, "numbers": nums}))
        w, lo = worst.setdefault(mode, {}), least.setdefault(mode, {})
        for k, v in nums.items():
            w[k], lo[k] = max(w.get(k, v), v), min(lo.get(k, v), v)

    base, refs = build(), {}
    for seed in seeds:
        base.reseed(seed)
        ref = base.reference()
        refs[seed] = {i: ref(i) for i in range(N_CHECK)}
        if control:
            record("control", seed, control_numbers(base, refs[seed].__getitem__))
    for mode in (["program"] if program else []) + [f"fault:{f}" for f in faults or ()]:
        if mode == "program":
            driver = base
            driver.setup()
        else:
            driver = build(mode.split(":", 1)[1])
        for seed in seeds:
            driver.reseed(seed)
            record(mode, seed, program_numbers(driver, refs[seed].__getitem__))
        driver.release()
        del driver
        gc.collect()
    out(json.dumps({"workload": cell.name, "largest": worst, "least": least, "limits": cell.limits}))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="", help="comma-separated names from bench/harness/faults.py")
    args = ap.parse_args(argv)

    from bench.harness.faults import FAULTS

    faults = [f for f in args.fault.split(",") if f]
    unknown = sorted(set(faults) - set(FAULTS))
    if unknown:
        ap.error(f"unknown fault(s) {unknown}; known: {sorted(FAULTS)}")
    cell = run.load_cell(ROOT, args.workload)
    devices = run.start_jax(cell)
    if not devices:
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(cell, seeds, args.program, args.control, faults, out=lambda s: print(s, flush=True), devices=devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
