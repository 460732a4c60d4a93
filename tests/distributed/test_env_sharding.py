"""Determinism + mesh-sharding equivalence for env-batch placement.

On a 1-device mesh every helper must degrade gracefully (constraints lower
to no-ops) and the sharded program must reproduce the unsharded one.  The CI
sharding job re-runs this file under ``JAX_PLATFORMS=cpu`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so the genuinely
multi-device path (station/env axis split across 2 host devices) is
exercised on every push; the device-count-gated asserts activate there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import ChargaxEnv, EnvConfig, FleetEnv
from repro.distributed import env_sharding, sharding
from repro.launch.mesh import make_data_mesh
from repro.rl import PPOConfig, make_train

jax.config.update("jax_platform_name", "cpu")

ENV = ChargaxEnv(EnvConfig())
SCEN_NAMES = ["shopping_flat", "shopping_pv_tou", "highway_demand_charge"]


def _tiny_cfg(num_envs=6, updates=2):
    return PPOConfig(
        total_timesteps=num_envs * 16 * updates,
        num_envs=num_envs,
        rollout_steps=16,
        num_minibatches=2,
        update_epochs=1,
        hidden=(16,),
    )


def _stacked():
    return scenarios.stack_params(
        [scenarios.make(n).make_params(ENV) for n in SCEN_NAMES]
    )


# ---------------------------------------------------------------------------
# tentpole acceptance: tables materialise with leading axis S, not num_envs
# ---------------------------------------------------------------------------
def test_scenario_tables_one_copy_per_scenario():
    stacked = _stacked()
    cfg = _tiny_cfg(num_envs=6)
    train = make_train(cfg, ENV, scenario_params=stacked)
    assert train.scenario_shape == (3, 2)
    lowered = jax.tree_util.tree_leaves(train.lowered_env_params)
    source = jax.tree_util.tree_leaves(stacked)
    assert len(lowered) == len(source)
    for got, src in zip(lowered, source):
        assert got.shape == src.shape  # identical to the (S, ...) catalog
        assert got.shape[0] == len(SCEN_NAMES)
        assert got.shape[0] != cfg.num_envs  # never one copy per env
    # and the nested-vmap program actually trains
    out = jax.jit(train)(jax.random.key(0))
    assert np.isfinite(np.asarray(out["metrics"]["loss"])).all()


def test_scenario_envs_must_divide():
    with pytest.raises(ValueError, match="drop scenarios"):
        make_train(_tiny_cfg(num_envs=4), ENV, scenario_params=_stacked())


# ---------------------------------------------------------------------------
# determinism: same key => bit-identical PPO metrics on CPU
# ---------------------------------------------------------------------------
def test_ppo_metrics_bit_identical_same_key():
    cfg = _tiny_cfg(num_envs=6)
    stacked = _stacked()
    key = jax.random.key(7)
    runs = []
    for _ in range(2):  # two fresh train closures, two fresh jits
        train = jax.jit(make_train(cfg, ENV, scenario_params=stacked))
        runs.append(jax.device_get(train(key)["metrics"]))
    a, b = runs
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# sharding equivalence: mesh-sharded programs match unsharded ones
# ---------------------------------------------------------------------------
def _fleet_rollout(fleet, params, steps=48):
    @jax.jit
    def rollout(key):
        _, state = fleet.reset(key, params)

        def body(carry, _):
            key, state = carry
            key, ka, ks = jax.random.split(key, 3)
            action = jax.random.randint(
                ka,
                (fleet.n_stations, fleet.num_action_heads),
                0,
                fleet.num_actions_per_head,
            )
            _, state, r, d, info = fleet.step(ks, state, action, params)
            return (key, state), (r, info["fleet_profit"])

        (_, state), (rewards, fprofit) = jax.lax.scan(body, (key, state), None, steps)
        return state.profit_cum, rewards, fprofit

    return jax.device_get(rollout(jax.random.key(11)))


def test_sharded_fleet_rollout_matches_unsharded():
    n_dev = jax.device_count()
    # station count a multiple of the device count so the mesh engages
    archs = ["paper_16", "deep_4x4"] * n_dev
    mesh = make_data_mesh()
    assert mesh.shape["data"] == n_dev

    ref = _fleet_rollout(FleetEnv(archs, shard=False), None)
    fleet = FleetEnv(archs)
    with jax.sharding.set_mesh(mesh):
        params = env_sharding.place_env_batch(fleet.default_params, mesh)
        if n_dev > 1:
            # tables really are distributed over the devices
            leaf = params.evse_mask
            assert len(leaf.sharding.device_set) == n_dev
        got = _fleet_rollout(fleet, params)

    for a, b, name in zip(got, ref, ("profit_cum", "rewards", "fleet_profit")):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


def test_sharded_scenario_ppo_matches_unsharded():
    """Nested-vmap PPO with the env batch constrained onto the mesh must
    reproduce the single-device run to float tolerance."""
    n_dev = jax.device_count()
    cfg = _tiny_cfg(num_envs=3 * 2 * n_dev)
    stacked = _stacked()
    key = jax.random.key(3)

    ref = jax.device_get(
        jax.jit(make_train(cfg, ENV, scenario_params=stacked))(key)["metrics"]
    )
    mesh = make_data_mesh()
    with jax.sharding.set_mesh(mesh):
        train = make_train(
            cfg,
            ENV,
            scenario_params=stacked,
            shard_envs=env_sharding.make_shard_envs(mesh),
        )
        got = jax.device_get(jax.jit(train)(key)["metrics"])

    for la, lb in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=1e-4, atol=1e-5
        )


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices (CI sharding job)")
def test_two_device_mesh_distributes_env_batch():
    """Acceptance: a 2-host-device CPU mesh really splits the station axis."""
    mesh = make_data_mesh()
    n_dev = jax.device_count()
    fleet = FleetEnv(["paper_16"] * (2 * n_dev))
    with jax.sharding.set_mesh(mesh):
        params = env_sharding.place_env_batch(fleet.default_params, mesh)
        obs, state = jax.jit(fleet.reset)(jax.random.key(0), params)
    assert len(params.evse_mask.sharding.device_set) == n_dev
    assert len(obs.sharding.device_set) == n_dev
    # per-device shard covers 1/n of the stations
    shard = obs.addressable_shards[0]
    assert shard.data.shape[0] == obs.shape[0] // n_dev


# ---------------------------------------------------------------------------
# graceful fallback
# ---------------------------------------------------------------------------
def test_constrain_env_batch_noop_without_mesh():
    x = jnp.ones((4, 3))
    tree = {"a": x, "b": jnp.float32(1.0)}
    out = env_sharding.constrain_env_batch(tree)
    assert out["a"] is x  # literally untouched: no annotation, no copy


def test_env_shardings_replicate_indivisible_leaves():
    mesh = make_data_mesh()
    tree = {"big": jnp.ones((4 * jax.device_count(), 2)), "odd": jnp.ones((3,))}
    sh = env_sharding.env_shardings(tree, mesh)
    if jax.device_count() > 1:
        assert sh["big"].spec == jax.sharding.PartitionSpec("data")
        assert sh["odd"].spec == jax.sharding.PartitionSpec()
    placed = env_sharding.place_env_batch(tree, mesh)
    np.testing.assert_array_equal(np.asarray(placed["big"]), np.asarray(tree["big"]))
