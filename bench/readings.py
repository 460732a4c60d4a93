#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/`` are set from.

    python3 bench/readings.py --workload ppo.paper16_shop --seeds 1,2,3 --program --control
    python3 bench/readings.py --workload sim.paper16_shop --seeds 1,2,3 --fault half_batch

For each seed, in one process and on the cell's own sizes: ``--program``
runs the cell's first calls and compares them with the reference, as a run
does (the lower readings); ``--control`` puts the reference, computed one
precision lower (bfloat16), in the program's place (the upper readings);
``--fault`` plants one of ``bench/harness/faults.py`` under the program.
Prints one JSON line per seed and mode, then the largest reading of each
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
    driver.kept = jax.device_get(driver.kept)
    return driver.numbers(driver.kept, ref_of)


def control_numbers(driver, ref_of) -> dict:
    import jax.numpy as jnp

    from bench.harness.drivers import N_CHECK

    low = driver.reference(jnp.bfloat16)
    got = {i: driver.as_output(low(i)) for i in range(N_CHECK)}
    return driver.numbers(got, ref_of)


def readings(cell, seeds: list[int], program: bool, control: bool, fault: str | None, out=print) -> dict:
    """{mode: {number: largest reading over the seeds}}; one line per seed to ``out``."""
    import contextlib

    from bench.harness.drivers import DRIVERS
    from bench.harness.faults import FAULTS

    kind = cell.traffic["driver"]
    plant = FAULTS[fault](kind) if fault else contextlib.nullcontext()
    with plant:
        driver = DRIVERS[kind](cell.config, cell.traffic, seeds[0], cell.recorded_tables)
        if program or fault:
            driver.setup()
    modes = {}
    if program or fault:
        modes[f"fault:{fault}" if fault else "program"] = program_numbers
    if control:
        modes["control"] = control_numbers
    worst: dict = {}
    for seed in seeds:
        driver.reseed(seed)
        ref, done = driver.reference(), {}

        def ref_of(i):  # the reference's calls, made once for every mode
            if i not in done:
                done[i] = ref(i)
            return done[i]

        for mode, fn in modes.items():
            nums = fn(driver, ref_of)
            out(json.dumps({"workload": cell.name, "seed": seed, "mode": mode, "numbers": nums}))
            w = worst.setdefault(mode, {})
            for k, v in nums.items():
                w[k] = max(w.get(k, v), v)
    out(json.dumps({"workload": cell.name, "largest": worst, "limits": cell.limits}))
    return worst


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("unchanged_state", "half_batch", "altered_answer"))
    args = ap.parse_args(argv)

    cell = run.load_cell(ROOT, args.workload)
    if not run.start_jax(cell):
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(cell, seeds, args.program, args.control, args.fault, out=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
