"""The benchmark's run path at a tiny size on the host CPU: the result line,
the env-step count per call, the refusal to run without a TPU, and the
readings the limits are set from (the control outside them)."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench.tests.tiny import ROOT, run_tiny, tiny_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", "sim.paper16_shop",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT,
    )
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "Nothing was measured" in p.stderr


@pytest.mark.parametrize("workload,steps", [("ppo.paper16_shop", 8 * 4), ("sim.paper16_shop", 8 * 288)])
def test_tiny_run_line(workload, steps):
    rc, line, cell = run_tiny(workload)
    assert rc == 0
    assert set(line) == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    rate = line["metrics"][cell.rate_metric]
    assert set(line["metrics"]) == {cell.rate_metric, "setup_s"} and rate["unit"] == "env-steps/s"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())

    from bench.harness.drivers import DRIVERS

    d = DRIVERS[cell.traffic["driver"]](cell.config, cell.traffic, 1)
    assert d.env_steps_per_call == steps


@pytest.mark.parametrize("workload", ["ppo.paper16_shop", "sim.paper16_shop"])
def test_readings_control_fails_program_passes(workload):
    from bench import readings

    cell = tiny_cell(workload)
    lines = []
    worst = readings.readings(cell, [4, 2**31 + 9], True, True, None, out=lines.append)
    assert len(lines) == 5
    # the control fails a limit; at this size the limits, set from readings
    # at the cell's own size, do not bind the program, which reads lower
    failed = [k for k, v in worst["control"].items() if v > cell.limits[k]]
    assert failed
    assert all(worst["program"][k] < worst["control"][k] for k in failed)
