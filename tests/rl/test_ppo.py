"""RL stack tests: networks, GAE oracle, learning on an easy objective."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ChargaxEnv, EnvConfig
from repro.rl import PPOConfig, evaluate, make_ppo_policy, make_train
from repro.rl import networks
from repro.rl.baselines import BASELINES


def test_actor_critic_shapes():
    key = jax.random.key(0)
    params = networks.init_actor_critic(key, obs_dim=33, n_heads=5, n_actions=7, hidden=(32,))
    obs = jnp.ones((4, 33))
    out = networks.apply_actor_critic(params, obs, 5, 7)
    assert out.logits.shape == (4, 5, 7)
    assert out.value.shape == (4,)


def test_factorized_logprob_and_entropy():
    key = jax.random.key(1)
    logits = jax.random.normal(key, (3, 2, 4))
    action = jnp.zeros((3, 2), jnp.int32)
    lp = networks.log_prob(logits, action)
    expected = jax.nn.log_softmax(logits, -1)[:, :, 0].sum(-1)
    np.testing.assert_allclose(lp, expected, rtol=1e-5)
    # uniform logits -> entropy = heads * log(K)
    ent = networks.entropy(jnp.zeros((1, 2, 4)))
    np.testing.assert_allclose(ent, 2 * np.log(4), rtol=1e-5)


@pytest.mark.parametrize("n_heads,n_levels", [(17, 21), (55, 21), (2, 4)])
def test_log_prob_select_is_gather_bit_for_bit(n_heads, n_levels):
    """``log_prob`` picks by compare-and-select; forward and gradient equal
    the ``take_along_axis`` form bit for bit, actions 0 and K-1 included."""
    k_logits, k_act, k_w = jax.random.split(jax.random.key(3), 3)
    logits = 3.0 * jax.random.normal(k_logits, (4096, n_heads, n_levels))
    action = jax.random.randint(k_act, (4096, n_heads), 0, n_levels)
    action = action.at[0].set(0).at[1].set(n_levels - 1)
    weight = jax.random.normal(k_w, (4096,))

    def gather_form(lg):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return jnp.take_along_axis(logp, action[..., None], axis=-1)[..., 0].sum(-1)

    got = jax.jit(networks.log_prob)(logits, action)
    want = jax.jit(gather_form)(logits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    grad_got = jax.jit(jax.grad(lambda lg: (networks.log_prob(lg, action) * weight).sum()))(logits)
    grad_want = jax.jit(jax.grad(lambda lg: (gather_form(lg) * weight).sum()))(logits)
    np.testing.assert_array_equal(np.asarray(grad_got), np.asarray(grad_want))


def test_orthogonal_init_is_orthogonal():
    w = networks.orthogonal(jax.random.key(2), (16, 16), scale=1.0)
    np.testing.assert_allclose(np.asarray(w @ w.T), np.eye(16), atol=1e-4)


def test_gae_matches_oracle():
    """GAE inside make_train is scanned; check the recurrence on a toy case."""
    gamma, lam = 0.9, 0.8
    rewards = np.array([1.0, 0.0, 2.0], np.float32)
    values = np.array([0.5, 0.4, 0.3], np.float32)
    dones = np.array([0.0, 0.0, 0.0], np.float32)
    last_val = 0.2
    # oracle: backward recursion
    adv = np.zeros(3, np.float32)
    next_v, gae = last_val, 0.0
    for t in reversed(range(3)):
        delta = rewards[t] + gamma * next_v * (1 - dones[t]) - values[t]
        gae = delta + gamma * lam * (1 - dones[t]) * gae
        adv[t] = gae
        next_v = values[t]

    def scan_fn(carry, t):
        gae, next_value = carry
        r, v, d = t
        delta = r + gamma * next_value * (1 - d) - v
        gae = delta + gamma * lam * (1 - d) * gae
        return (gae, v), gae

    _, out = jax.lax.scan(
        scan_fn,
        (jnp.float32(0.0), jnp.float32(last_val)),
        (jnp.asarray(rewards), jnp.asarray(values), jnp.asarray(dones)),
        reverse=True,
    )
    np.testing.assert_allclose(out, adv, rtol=1e-5)


@pytest.mark.slow
def test_ppo_improves_reward():
    """A short run must improve mean rollout reward over its own start."""
    env = ChargaxEnv(EnvConfig(traffic="high"))
    cfg = PPOConfig(total_timesteps=90_000, num_envs=6, rollout_steps=150, hidden=(64, 64))
    train = jax.jit(make_train(cfg, env))
    out = train(jax.random.key(0))
    rr = np.asarray(out["metrics"]["rollout_reward"])
    assert np.isfinite(rr).all()
    # compare mean of first vs last quartile of updates
    q = max(len(rr) // 4, 1)
    assert rr[-q:].mean() > rr[:q].mean()


def test_baselines_produce_valid_actions():
    env = ChargaxEnv(EnvConfig())
    obs, _ = env.reset(jax.random.key(0))
    for name, make in BASELINES.items():
        pol = make(env)
        a = pol(None, jax.random.key(1), obs)
        assert a.shape == (env.num_action_heads,), name
        assert bool((a >= 0).all() and (a < env.num_actions_per_head).all()), name


def test_evaluate_runs():
    env = ChargaxEnv(EnvConfig())
    res = evaluate(env, BASELINES["max_charge"](env), None, jax.random.key(0), 4)
    assert res["cars_served"] > 0
    assert np.isfinite(res["episode_reward"])


def test_evaluate_params_axis_maps_stacked_params():
    """Regression: evaluate used to hard-code in_axes=(0, 0, 0, None), so a
    stacked (S, ...) scenario/fleet parameter pytree could not be evaluated
    per-episode.  params_axis=0 maps one stacked slice per episode."""
    from repro import scenarios

    env = ChargaxEnv(EnvConfig())
    names = ["shopping_flat", "highway_demand_charge"]
    stacked = scenarios.stack_params(
        [scenarios.make(n).make_params(env) for n in names]
    )
    pol = BASELINES["max_charge"](env)
    res = evaluate(
        env, pol, None, jax.random.key(0),
        num_episodes=len(names), env_params=stacked, params_axis=0,
    )
    assert res["cars_served"] > 0
    assert np.isfinite(res["episode_reward"])

    # the two worlds genuinely differ: per-episode metrics must not collapse
    # to the broadcast single-params result for both scenarios
    res_flat = evaluate(
        env, pol, None, jax.random.key(0),
        num_episodes=len(names),
        env_params=scenarios.make("shopping_flat").make_params(env),
    )
    assert res["daily_profit"] != pytest.approx(res_flat["daily_profit"])

    # stacked size must match num_episodes, loudly
    with pytest.raises(ValueError, match="must equal the stacked"):
        evaluate(
            env, pol, None, jax.random.key(0),
            num_episodes=4, env_params=stacked, params_axis=0,
        )
