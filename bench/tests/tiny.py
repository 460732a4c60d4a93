"""A cell of ``BENCHMARK.json`` cut to a size a CPU test run holds."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import types
from contextlib import redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(workload: str):
    from bench import run

    cell = run.load_cell(ROOT, workload)
    cell.config["num_envs"] = 8
    cell.config["ppo"].update(rollout_steps=4, num_minibatches=2, update_epochs=1)
    cell.traffic["sample_envs"] = 4
    return cell


def run_tiny(workload: str, seed: int = 2**31 + 11, seconds: float = 0.2, n_devices: int = 1) -> tuple[int, dict, object]:
    """Drive ``run.run_cell`` on the host CPU's first ``n_devices`` (the chip
    check skipped); returns (exit code, last stdout line as JSON, the cell)."""
    import jax

    from bench import run

    cell = tiny_cell(workload)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds, trace=0)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.run_cell(cell, args, jax.devices("cpu")[:n_devices])
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), cell


def on_devices(workload: str, n_devices: int, fault: str | None = None) -> dict:
    """``run_tiny`` of a PPO cell on ``n_devices`` CPU devices, with a fault
    of ``bench/harness/faults.py`` planted where named: the result line, the
    losses of the checked calls, and what of the env batch the compiled
    program holds whole when read as a four-device program."""
    import contextlib

    import numpy as np

    from bench.harness import drivers
    from bench.harness.faults import FAULTS

    made = []

    class Kept(drivers.PPOUpdate):
        def setup(self):
            super().setup()
            made.append((self, self.hlo_text()))

    base = drivers.DRIVERS["ppo_update"]
    drivers.DRIVERS["ppo_update"] = Kept
    try:
        with FAULTS[fault]("ppo_update") if fault else contextlib.nullcontext():
            rc, line, cell = run_tiny(workload, n_devices=n_devices)
    finally:
        drivers.DRIVERS["ppo_update"] = base
    driver, hlo = made[0]
    c = cell.config
    whole = drivers.undivided(
        hlo, c["num_envs"], 4, len(c["scenarios"]), c["ppo"]["rollout_steps"],
        driver.shapes["obs_dim"], driver.shapes["n_evse"],
    )
    losses = [float(np.asarray(driver.kept[i]["metrics"]["loss"])) for i in sorted(driver.kept)]
    return {"rc": rc, "line": line, "losses": losses, "undivided": whole}


def on_four_devices(workload: str, runs: list[tuple[int, str | None]]) -> list[dict]:
    """``on_devices`` for each (devices, fault) in one child process that
    sees four CPU devices."""
    code = (
        "import json\n"
        "from bench.tests.tiny import on_devices\n"
        f"for n, fault in {runs!r}:\n"
        f"    print('RESULT ' + json.dumps(on_devices({workload!r}, n, fault)), flush=True)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=1200, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return [json.loads(line[len("RESULT "):]) for line in p.stdout.splitlines() if line.startswith("RESULT ")]
