"""A cell of ``BENCHMARK.json`` cut to a size a CPU test run holds."""
from __future__ import annotations

import io
import json
import os
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


def run_tiny(workload: str, seed: int = 2**31 + 11, seconds: float = 0.2) -> tuple[int, dict, object]:
    """Drive ``run.run_cell`` on the host CPU (the chip check skipped);
    returns (exit code, last stdout line as JSON, the cell)."""
    import jax

    from bench import run

    cell = tiny_cell(workload)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds, trace=0)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.run_cell(cell, args, jax.devices("cpu")[:1])
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), cell
