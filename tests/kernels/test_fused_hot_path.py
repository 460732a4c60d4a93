"""The fused hot-path flag is bit-identical to the staged pipeline.

Acceptance criteria of ISSUE 10: ``fused_step=True`` proven bit-identical to
staged across all four action modes and dt ∈ {5, 15, 60}, under jit, vmap and
the nested scenario×env layout — all through the shared parity harness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.envs.wrappers import AutoReset, VmapWrapper
from repro.kernels.chargax_step import ops as fused_ops


@pytest.mark.parametrize("mode", sorted(harness.ACTION_MODES))
@pytest.mark.parametrize("dt", harness.DT_MINUTES)
def test_fused_transition_bit_identical(mode, dt):
    """request+allocate+deliver: fused(ref) == staged, bitwise, under jit."""
    env = harness.make_env(mode, dt)
    params = env.default_params
    for seed in range(6):
        state = harness.random_state(
            env, params, jax.random.key(seed), n_occupied=seed % (env.n_evse + 1)
        )
        te, tb = harness.random_targets(params, jax.random.key(seed + 1000))
        harness.assert_fused_matches_staged(env, params, state, te, tb)


@pytest.mark.parametrize("mode", sorted(harness.ACTION_MODES))
def test_fused_env_step_bit_identical(mode):
    """Full env.step TimeStep (obs/state/reward/done/info): fused == staged."""
    env = harness.make_env(mode)
    params = env.default_params
    for seed in range(4):
        state = harness.random_state(env, params, jax.random.key(seed))
        action = harness.random_action(env, jax.random.key(seed + 50))
        harness.assert_step_matches(env, params, state, action, jax.random.key(seed))


if harness.HAVE_HYPOTHESIS:
    from hypothesis import HealthCheck, given, settings

    @settings(
        max_examples=25, deadline=None, suppress_health_check=[HealthCheck.too_slow]
    )
    @given(case=harness.parity_cases())
    def test_fused_matches_staged_hypothesis(case):
        """Property sweep: modes × dt × ragged architectures × random states."""
        env, params, state, te, tb = case
        harness.assert_fused_matches_staged(env, params, state, te, tb)


def test_fused_rollout_vmap_bit_identical():
    """40-step jitted vmapped rollout through the wrapper stack: fused ==
    staged bitwise on every TimeStep leaf."""
    env = harness.make_env("v2g")
    n_envs, n_steps = 16, 40

    def rollout(wenv, params):
        obs0, st0 = wenv.reset(jax.random.key(0), params)
        acts = jax.random.randint(
            jax.random.key(1),
            (n_steps, n_envs, env.num_action_heads),
            0,
            env.num_actions_per_head,
        )

        def body(carry, xs):
            k, a = xs
            ts = wenv.step(k, carry, a, params)
            return ts.state, (ts.obs, ts.reward, ts.done, ts.info)

        keys = jax.random.split(jax.random.key(2), n_steps)
        return jax.jit(lambda s: jax.lax.scan(body, s, (keys, acts)))(st0)

    staged = rollout(AutoReset(VmapWrapper(env, n_envs)), env.default_params)
    fenv = env.with_fused_step(True)
    fused = rollout(AutoReset(VmapWrapper(fenv, n_envs)), fenv.default_params)
    harness.assert_trees_equal(fused[1], staged[1], "rollout outputs")


def test_fused_nested_scenario_env_layout_bit_identical():
    """The nested scenario×env VmapWrapper layout: fused == staged bitwise."""
    scen = pytest.importorskip("repro.scenarios")
    env = harness.make_env()
    fenv = env.with_fused_step(True)
    names = ["shopping_pv_tou", "grid_tight_transformer"]
    sp = scen.stack_params([scen.make(n).make_params(env) for n in names])
    fsp = scen.stack_params([scen.make(n).make_params(fenv) for n in names])
    n_envs = 8
    action = jnp.zeros((n_envs, env.num_action_heads), jnp.int32) + 14

    def run(e, params):
        w = VmapWrapper(e, n_envs, num_scenarios=len(names))
        obs, st = w.reset(jax.random.key(5), params)
        return jax.jit(lambda s: w.step(jax.random.key(6), s, action, params))(st)

    ts_s = run(env, sp)
    ts_f = run(fenv, fsp)
    harness.assert_trees_equal(ts_f, ts_s, "nested scenario×env TimeStep")


def test_fused_grid_scenario_curtailment_bit_identical():
    """A finite feeder cap (grid scenario) takes the cap-active branch of the
    fused allocate fold and still matches staged bitwise."""
    scen = pytest.importorskip("repro.scenarios")
    env = harness.make_env()
    params = scen.make("grid_tight_transformer").make_params(env)
    for seed in range(4):
        state = harness.random_state(env, params, jax.random.key(seed), 16)
        te = jnp.broadcast_to(params.evse_max_current, (env.n_evse,)) * 1.0
        tb = params.batt_max_current * 1.0
        harness.assert_fused_matches_staged(env, params, state, te, tb)
        # the cap must actually bind somewhere in this sweep
    alloc, _ = fused_ops.fused_transition(
        harness.fused_params(params), state, te, tb, env.config.dt_hours, impl="ref"
    )
    assert float(alloc.cap_kw) < 1e8  # finite cap table is in force


def test_fused_pallas_interpret_close_on_hot_path():
    """The Pallas kernel (interpret mode — what TPU/GPU dispatch runs) agrees
    with staged within fp32 op-reorder tolerance on the same transition."""
    env = harness.make_env("v2g", 15.0)
    params = env.default_params
    state = harness.random_state(env, params, jax.random.key(11))
    te, tb = harness.random_targets(params, jax.random.key(12))
    harness.assert_fused_close(env, params, state, te, tb, impl="interpret")


def test_resolve_impl_env_var_override(monkeypatch):
    """CHARGAX_FUSED_IMPL forces the backend; auto falls back per-platform."""
    monkeypatch.setenv(fused_ops.IMPL_ENV_VAR, "interpret")
    assert fused_ops.resolve_impl() == "interpret"
    monkeypatch.setenv(fused_ops.IMPL_ENV_VAR, "pallas")
    assert fused_ops.resolve_impl() == "pallas"
    monkeypatch.delenv(fused_ops.IMPL_ENV_VAR)
    expected = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert fused_ops.resolve_impl() == expected
    assert fused_ops.resolve_impl("ref") == "ref"  # explicit beats env/auto


def test_fused_params_pole_pack_hoisted():
    """make_params attaches the pole pack only when the flag is on, and the
    per-step builder reuses the hoisted pack as-is."""
    env = harness.make_env()
    fenv = env.with_fused_step(True)
    assert env.default_params.pole is None
    pp = fenv.default_params.pole
    assert pp is not None
    assert fused_ops.build_pole_params(fenv.default_params) is pp
    # power_w row: evse_voltage/path_eff on real lanes, batt_voltage after
    p = env.default_params
    np.testing.assert_allclose(
        np.asarray(pp.power_w[: env.n_evse]),
        np.asarray(p.evse_voltage / p.evse_path_eff),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        float(pp.power_w[env.n_evse]), float(p.batt_voltage), rtol=1e-6
    )
    assert np.all(np.asarray(pp.power_w[env.n_evse + 1 :]) == 0.0)
