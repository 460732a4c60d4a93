"""LogWrapper episode accounting + KPI accumulation across AutoReset
boundaries: the scan path matches a host Python loop bit-for-bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChargaxEnv, EnvConfig
from repro.envs import AutoReset, LogWrapper, VmapWrapper
from repro.utils import replace

jax.config.update("jax_platform_name", "cpu")

N_ENVS = 3
METRICS = ("reward", "profit", "energy_delivered", "missing_kwh")


def _stack():
    env = ChargaxEnv(EnvConfig(episode_hours=1.0))  # 12 steps per episode
    wenv = LogWrapper(AutoReset(VmapWrapper(env, N_ENVS)), metrics=METRICS)
    return env, wenv


def test_episode_accounting_across_autoreset_boundaries():
    env, wenv = _stack()
    params = env.default_params
    ep_steps = env.config.episode_steps
    t_total = 2 * ep_steps + 5  # crosses two episode boundaries

    obs, state = wenv.reset(jax.random.key(0), params)
    step = jax.jit(wenv.step)
    action = wenv.sample_action(jax.random.key(1))
    keys = jax.random.split(jax.random.key(2), t_total)

    ep_ret = np.zeros(N_ENVS, np.float32)  # sequential float32 reference
    ep_len = 0
    boundaries = 0
    for t in range(t_total):
        ts = step(keys[t], state, action, params)
        state = ts.state
        ep_ret = (ep_ret + np.asarray(ts.reward)).astype(np.float32)
        ep_len += 1
        if bool(np.all(np.asarray(ts.done))):
            boundaries += 1
            assert ep_len == ep_steps
            # the finishing episode's totals are surfaced, bit-for-bit
            assert np.asarray(ts.info["returned_episode"]).all()
            assert (
                np.asarray(ts.info["episode_return"]).tobytes() == ep_ret.tobytes()
            )
            assert (np.asarray(ts.info["episode_length"]) == ep_steps).all()
            # running totals restart with the fresh episode
            assert (np.asarray(state.episode_return) == 0.0).all()
            assert (np.asarray(state.episode_length) == 0).all()
            ep_ret = np.zeros(N_ENVS, np.float32)
            ep_len = 0
        else:
            assert not np.asarray(ts.done).any()
            # between boundaries the returned totals stay frozen
            assert (
                np.asarray(ts.info["episode_length"])
                == (ep_steps if boundaries else 0)
            ).all()
    assert boundaries == 2


def test_metrics_accumulate_through_resets_and_match_python_loop():
    env, wenv = _stack()
    params = env.default_params
    t_total = env.config.episode_steps + 7  # crosses one boundary

    obs, state0 = wenv.reset(jax.random.key(0), params)
    action = wenv.sample_action(jax.random.key(1))
    keys = jax.random.split(jax.random.key(2), t_total)
    step = jax.jit(wenv.step)

    # host loop reference: sequential float32 accumulation of info scalars
    state = state0
    ref = {n: np.zeros(N_ENVS, np.float32) for n in METRICS}
    for t in range(t_total):
        ts = step(keys[t], state, action, params)
        state = ts.state
        ref["reward"] = (ref["reward"] + np.asarray(ts.reward)).astype(np.float32)
        for n in METRICS[1:]:
            ref[n] = (ref[n] + np.asarray(ts.info[n])).astype(np.float32)
    loop_acc = state.metrics

    # same steps as ONE jitted rollout scan; emit the per-step values the
    # scan itself computed (XLA may fuse the env math differently inside a
    # scan than in a per-step jit, shifting rewards by 1 ulp — the claim
    # under test is that ACCUMULATION is bit-exact, not that fusion is)
    @jax.jit
    def rollout(state):
        def body(carry, key):
            ts = wenv.step(key, carry, action, params)
            return ts.state, {"reward": ts.reward, **{n: ts.info[n] for n in METRICS[1:]}}

        return jax.lax.scan(body, state, keys)

    scan_final, per_step = rollout(state0)
    scan_acc = scan_final.metrics
    scan_ref = {n: np.zeros(N_ENVS, np.float32) for n in METRICS}
    for t in range(t_total):
        for n in METRICS:
            scan_ref[n] = (
                scan_ref[n] + np.asarray(per_step[n])[t]
            ).astype(np.float32)

    assert float(loop_acc.count.min()) == t_total  # reset did NOT clear KPIs
    for n in METRICS:
        got_loop = np.asarray(loop_acc.sums[n])
        got_scan = np.asarray(scan_acc.sums[n])
        assert got_loop.tobytes() == ref[n].tobytes(), n
        # in-scan accumulator == host float32 loop over the scan's own values
        assert got_scan.tobytes() == scan_ref[n].tobytes(), n
        assert np.allclose(got_scan, ref[n], rtol=1e-5), n

    out = scan_acc.flush(means=("reward",))
    assert out["steps"] == t_total
    assert np.isfinite(out["reward_per_step"])
    assert out["energy_delivered"] >= 0.0


def test_metrics_default_off_keeps_state_lean():
    env = ChargaxEnv(EnvConfig(episode_hours=1.0))
    wenv = LogWrapper(AutoReset(VmapWrapper(env, 2)))
    obs, state = wenv.reset(jax.random.key(0), env.default_params)
    assert state.metrics is None
    ts = wenv.step(
        jax.random.key(1), state, wenv.sample_action(jax.random.key(2)),
        env.default_params,
    )
    assert ts.state.metrics is None


def test_constraint_binds_is_the_share_of_steps_scaled_by_eq5():
    """``constraint_binds`` is derived from ``info["constraint_excess"]``,
    so its per-step mean is the share of env-steps in which some Eq. 5
    constraint bound (here on the Caltech garage at its busiest hours)."""
    env = ChargaxEnv(EnvConfig(architecture="caltech_acn54", battery=False, scenario="work"))
    params = env.default_params
    wenv = LogWrapper(AutoReset(VmapWrapper(env, N_ENVS)), metrics=("constraint_binds",))
    _, state = wenv.reset(jax.random.key(0), params)
    inner = replace(state.env_state, t=jnp.full((N_ENVS,), 8 * 12, jnp.int32))  # 08:00
    state = state._replace(env_state=inner)
    top = jnp.full((N_ENVS, env.num_action_heads), 2 * env.config.discretization, jnp.int32)

    @jax.jit
    def rollout(state):
        def body(s, key):
            ts = wenv.step(key, s, top, params)
            return ts.state, ts.info["constraint_excess"] > 0.0

        return jax.lax.scan(body, state, jax.random.split(jax.random.key(1), 36))

    final, bound = rollout(state)
    share = np.asarray(bound, np.float32).mean(axis=0)
    got = np.asarray(final.metrics.sums["constraint_binds"]) / 36.0
    np.testing.assert_allclose(got, share, rtol=1e-6)
    assert 0.0 < share.mean() < 1.0
    assert final.metrics.flush(means=("constraint_binds",))["constraint_binds_per_step"] == pytest.approx(float(share.mean()))
