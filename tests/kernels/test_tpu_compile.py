"""Compile the main-path kernel programs for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned slices, more scoped VMEM than a kernel may use) — failures that
interpret mode cannot show.  The topology is described inside a fixture, so
only the worker that runs these tests loads libtpu; where it cannot be
described, every test here skips.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import scenarios
from repro.core import ChargaxEnv, EnvConfig
from repro.envs import AutoReset, VmapWrapper
from repro.kernels.chargax_step import ops
from repro.kernels.chargax_step.kernel import chargax_fused_step
from repro.obs import enable_trace_annotations

N_ENVS = 4096
P = 128  # one lane tile of poles: the 16-EVSE station + battery, padded
NN = 8  # one sublane tile of nodes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back here: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=sharding),
        tree,
    )


def _kernel_text(one_chip, block_envs: int) -> str:
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    slabs = tuple(f32(N_ENVS, P) for _ in range(7))
    params = tuple(f32(8, P) for _ in range(4)) + (f32(NN, P), f32(NN, 128))
    fn = lambda s, p, c: chargax_fused_step(s, p, c, dt_hours=5 / 60, block_envs=block_envs)
    return jax.jit(fn).lower(slabs, params, f32(N_ENVS, 128)).compile().as_text()


@pytest.mark.parametrize("block_envs", [256, 8])
def test_fused_kernel_compiles_for_v5e(one_chip, block_envs):
    """4096 envs x 128 poles x 8 nodes; 256 is ``fused_step``'s default
    block, 8 the block a per-env call under vmap pads to."""
    assert "tpu_custom_call" in _kernel_text(one_chip, block_envs)


def test_fused_vmapped_env_step_compiles_for_v5e(one_chip, monkeypatch):
    """The ``rl_train --fused`` hot path: one jitted ``VmapWrapper`` step of
    4096 fused envs lowers to the Pallas kernel."""
    monkeypatch.setenv(ops.IMPL_ENV_VAR, "pallas")
    venv = VmapWrapper(ChargaxEnv(EnvConfig(fused_step=True)), N_ENVS)
    params = venv.default_params
    key = jax.random.key(0)
    _, state = jax.eval_shape(venv.reset, key, params)
    action = jax.eval_shape(venv.sample_action, key)
    args = _spec((key, state, action, params), one_chip)
    text = jax.jit(venv.step).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_car_model_draw_and_lookup_compile_without_gathers_for_v5e(one_chip):
    """The benchmark's nested step (four shopping scenarios x 1024 envs, 16
    ports): the car-model draw and the model-table lookup compile to no
    ``while`` and no per-port gather. The one gather left under
    ``env/draw_model`` reads each env's row of its scenario's drift table."""
    names = ("shopping_flat", "shopping_pv_tou", "shopping_fleet_drift", "real_nl_2024_shopping_tou")
    env = ChargaxEnv(EnvConfig())
    params = scenarios.stack_params([scenarios.make(n).make_params(env) for n in names])
    venv = AutoReset(VmapWrapper(env, N_ENVS, num_scenarios=len(names)))
    key = jax.random.key(0)
    _, state = jax.eval_shape(venv.reset, key, params)
    action = jax.eval_shape(venv.sample_action, key)
    prev = enable_trace_annotations(True)
    try:
        text = jax.jit(venv.step).lower(*_spec((key, state, action, params), one_chip)).compile().as_text()
    finally:
        enable_trace_annotations(prev)
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"= .*? ([a-z][a-z0-9-]*)\(", line)
        if name and op and ("env/draw_model" in name.group(1) or "env/car_lookup" in name.group(1)):
            found.append((op.group(1), name.group(1)))
    assert any("env/car_lookup" in n for _, n in found)
    bad = [(op, n) for op, n in found if op in ("gather", "while")]
    assert [b for b in bad if not b[1].endswith("/env/draw_cars/env/draw_model/gather")] == []
