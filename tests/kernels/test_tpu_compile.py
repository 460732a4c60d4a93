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


def _scoped_ops(text: str, *scopes: str) -> list[tuple[str, str]]:
    """(opcode, op_name) of each compiled op whose op_name holds one of ``scopes``."""
    found = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"= .*? ([a-z][a-z0-9-]*)\(", line)
        if name and op and any(s in name.group(1) for s in scopes):
            found.append((op.group(1), name.group(1)))
    return found


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
    found = _scoped_ops(text, "env/draw_model", "env/car_lookup")
    assert any("env/car_lookup" in n for _, n in found)
    bad = [(op, n) for op, n in found if op in ("gather", "while")]
    assert [b for b in bad if not b[1].endswith("/env/draw_cars/env/draw_model/gather")] == []


def _ppo_logprob_programs(one_chip, obs_dim: int, n_heads: int, rows: int) -> dict:
    """The two programs that run ``networks.log_prob`` inside ``make_train``,
    built as it builds them: the rollout's act step (forward, sample, log-prob)
    at one chip's 4,096 envs, and the PPO loss gradient at one minibatch."""
    from repro.obs import annotate
    from repro.rl import networks

    n_levels = 21
    hidden = (128, 128)
    params = jax.eval_shape(
        lambda k: networks.init_actor_critic(k, obs_dim, n_heads, n_levels, hidden), jax.random.key(0)
    )

    def policy(p, obs):
        with annotate("ppo/mlp"):
            return networks.apply_actor_critic(p, obs, n_heads, n_levels)

    def log_prob(logits, action):
        with annotate("ppo/logprob"):
            return networks.log_prob(logits, action)

    def act(p, key, obs):
        out = policy(p, obs)
        action = networks.sample_action(key, out.logits)
        return action, log_prob(out.logits, action), out.value

    def loss(p, obs, action, old_logp, old_value, gae, targets):
        out = policy(p, obs)
        ratio = jnp.exp(log_prob(out.logits, action) - old_logp)
        gae_n = (gae - gae.mean()) / (gae.std() + 1e-8)
        pg = -jnp.minimum(ratio * gae_n, jnp.clip(ratio, 0.8, 1.2) * gae_n).mean()
        v_clip = old_value + jnp.clip(out.value - old_value, -10.0, 10.0)
        v = 0.5 * jnp.maximum(jnp.square(out.value - targets), jnp.square(v_clip - targets)).mean()
        return pg + 0.25 * v - 0.01 * networks.entropy(out.logits).mean()

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    act_args = _spec((params, key, f32(N_ENVS, obs_dim)), one_chip)
    loss_args = _spec((params, f32(rows, obs_dim), i32(rows, n_heads)) + (f32(rows),) * 4, one_chip)
    prev = enable_trace_annotations(True)
    try:
        return {
            "act": jax.jit(act).lower(*act_args).compile().as_text(),
            "loss_grad": jax.jit(jax.grad(loss)).lower(*loss_args).compile().as_text(),
        }
    finally:
        enable_trace_annotations(prev)


@pytest.mark.parametrize(
    "obs_dim,n_heads", [(137, 17), (441, 55)], ids=["paper16_17_heads", "caltech_55_heads"]
)
def test_ppo_log_prob_compiles_without_gathers_for_v5e(one_chip, obs_dim, n_heads):
    """``networks.log_prob`` in the rollout's act step (4,096 envs) and in the
    loss gradient of one minibatch (307,200 rows): the 21-level pick compiles
    to selects, with no gather under ``ppo/logprob``."""
    for program, text in _ppo_logprob_programs(one_chip, obs_dim, n_heads, 307_200).items():
        scoped = _scoped_ops(text, "ppo/logprob")
        assert scoped, f"{program}: no op under ppo/logprob"
        assert [s for s in scoped if s[0] == "gather"] == [], program
