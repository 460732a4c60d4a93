"""Dry-run machinery on a forced 8-device CPU mesh (2x4) — proves the
lower+compile+analysis path itself, independent of the 512-device runs.

NOTE: the 8-device forcing must happen before jax initialises, so this test
module is run in a subprocess by the wrapper test below when the parent
session already holds a 1-device backend.
"""
import json
import os
import subprocess
import sys

import pytest

_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import build_model, get_config
from repro.distributed import sharding as shd
from repro.distributed.train_step import TrainStepConfig, TrainState, make_train_step, make_serve_step
from repro.optim import AdamWState
from repro.analysis.hlo import collective_stats

mesh = jax.make_mesh((2, 4), ("data", "model"))
assert mesh.devices.size == 8

cfg = dataclasses.replace(
    get_config("tinyllama-1.1b", smoke=True),
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
)
model = build_model(cfg)
params_abs = jax.eval_shape(model.init, jax.random.key(0))
params_sh = shd.param_shardings(params_abs, mesh)
rep = NamedSharding(mesh, P())

step = make_train_step(model, TrainStepConfig(num_microbatches=2))
opt_abs = jax.eval_shape(
    lambda p: AdamWState(
        step=jnp.int32(0),
        mu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
        nu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
    ),
    params_abs,
)
state_abs = TrainState(params=params_abs, opt=opt_abs, error_feedback={})
state_sh = TrainState(params=params_sh, opt=AdamWState(step=rep, mu=params_sh, nu=params_sh), error_feedback={})
tok = jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))
batch = {"tokens": tok, "labels": tok}
batch_sh = jax.tree_util.tree_map(lambda s: s.sharding, batch)

lowered = jax.jit(step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,)).lower(state_abs, batch)
compiled = lowered.compile()
cost = compiled.cost_analysis() or {}
coll = collective_stats(compiled.as_text())
mem = compiled.memory_analysis()

# ALSO run it for real on the 8 fake devices (tiny): numbers must be finite
import numpy as np
params = jax.jit(model.init, out_shardings=params_sh)(jax.random.key(0))
from repro.optim import adamw_init
opt = adamw_init(params)
state = TrainState(params=params, opt=opt, error_feedback={})
tokens = jax.device_put(jnp.ones((8, 64), jnp.int32), NamedSharding(mesh, P("data", None)))
state, metrics = jax.jit(step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,))(state, {"tokens": tokens, "labels": tokens})
assert bool(jnp.isfinite(metrics["loss"])), metrics

print("RESULT", {
    "flops": float(cost.get("flops", -1)),
    "collective_count": coll["total_count"],
    "collective_bytes": coll["total_bytes"],
    "loss": float(metrics["loss"]),
})
"""


@pytest.mark.slow
def test_mini_mesh_dryrun_and_real_step(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    result = eval(line[len("RESULT ") :])
    assert result["flops"] > 0
    # a sharded train step must actually communicate
    assert result["collective_count"] > 0
    assert result["collective_bytes"] > 0
    import math

    assert math.isfinite(result["loss"])
