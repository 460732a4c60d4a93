"""Plain reference of one PPO update on the Chargax MDP (PureJaxRL PPO, the
Chargax paper's Table 3 settings), built on ``chargax_ref``.

It imports nothing of ``repro``: the actor-critic MLP, its orthogonal
initialisation, the factorised categorical policy, GAE, the clipped loss
and AdamW with global-norm clipping are written out here from their
published definitions, with the key discipline of a PureJaxRL update:

    key, k_net, k_reset = split(key, 3)      # init, env reset
    per rollout step: key, k_act, k_env = split(key, 3)
    per epoch:        key, k_perm = split(key)

``mlp_dtype`` is the type the network computes in: float32 (matmuls at the
chip's default precision, as the configuration states) or bfloat16 for the
lower-precision control.  ``shard`` places the env batch where the program
under test places it (the env state and observations after the reset, the
observations after each step); the identity leaves it to the compiler.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.reference import chargax_ref as ref


class UpdateResult(NamedTuple):
    init_params: dict
    params: dict
    mu: dict  # Adam's first moment after the update
    metrics: dict


def _orthogonal(key, n_in, n_out, scale):
    big = max(n_in, n_out)
    q, r = jnp.linalg.qr(jax.random.normal(key, (big, big), jnp.float32))
    q = q * jnp.sign(jnp.diag(r))[None, :]
    return scale * q[:n_in, :n_out]


def init_net(key, obs_dim, n_heads, n_levels, hidden):
    keys = jax.random.split(key, 2 * len(hidden) + 2)
    net = {"actor": {}, "critic": {}}
    d = obs_dim
    for i, h in enumerate(hidden):
        for j, part in enumerate(("actor", "critic")):
            net[part][f"h{i}"] = {"w": _orthogonal(keys[2 * i + j], d, h, math.sqrt(2.0)), "b": jnp.zeros((h,), jnp.float32)}
        d = h
    net["actor"]["out"] = {"w": _orthogonal(keys[-2], d, n_heads * n_levels, 0.01), "b": jnp.zeros((n_heads * n_levels,), jnp.float32)}
    net["critic"]["out"] = {"w": _orthogonal(keys[-1], d, 1, 1.0), "b": jnp.zeros((1,), jnp.float32)}
    return net


def forward(net, obs, n_heads, n_levels, mlp_dtype=jnp.float32):
    """Logits ``(..., heads, levels)`` and value ``(...)``, both float32."""

    def mlp(part):
        x = obs.astype(mlp_dtype)
        layers = net[part]
        for i in range(len(layers) - 1):
            x = jnp.tanh(x @ layers[f"h{i}"]["w"].astype(mlp_dtype) + layers[f"h{i}"]["b"].astype(mlp_dtype))
        return (x @ layers["out"]["w"].astype(mlp_dtype) + layers["out"]["b"].astype(mlp_dtype)).astype(jnp.float32)

    logits = mlp("actor").reshape(obs.shape[:-1] + (n_heads, n_levels))
    return logits, mlp("critic")[..., 0]


def _log_prob(logits, action):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(lp, action[..., None], axis=-1)[..., 0].sum(-1)


def _entropy(logits):
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -(jnp.exp(lp) * lp).sum(-1).sum(-1)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree)))


def make_update(tabs: dict, config: dict, mlp_dtype=jnp.float32, shard=None):
    """key -> UpdateResult of one PPO update from fresh weights and fresh
    envs, over the configuration's stacked scenario tables ``tabs``;
    ``shard`` (a pytree -> pytree callable, the identity by default) is
    applied to the env batch where the program constrains it."""
    shard = shard or (lambda tree: tree)
    env_cfg, ppo = config["env"], config["ppo"]
    n_envs = config["num_envs"]
    n_scen = jax.tree_util.tree_leaves(tabs)[0].shape[0]
    n_heads = tabs["evse_voltage"].shape[-1] + 1
    n_levels = 2 * env_cfg["discretization"] + 1
    obs_dim = 8 * (n_heads - 1) + 2 + 4 + 3
    T = ppo["rollout_steps"]
    n_mb, epochs = ppo["num_minibatches"], ppo["update_epochs"]
    bs = n_envs * T
    total_opt_steps = epochs * n_mb  # one update: the learning rate anneals to 0 over it
    gamma, lam, scale = ppo["gamma"], ppo["gae_lambda"], ppo["reward_scale"]
    b1, b2, eps = 0.9, 0.999, 1e-8

    def flat(x):
        return x.reshape((n_envs,) + x.shape[2:])

    def nest(x):
        return x.reshape((n_scen, n_envs // n_scen) + x.shape[1:])

    def loss_fn(net, obs, act, old_v, old_lp, adv, tgt):
        logits, v = forward(net, obs, n_heads, n_levels, mlp_dtype)
        ratio = jnp.exp(_log_prob(logits, act) - old_lp)
        a = (adv - adv.mean()) / (adv.std() + 1e-8)
        clip = ppo["clip_eps"]
        pg = -jnp.minimum(ratio * a, jnp.clip(ratio, 1 - clip, 1 + clip) * a).mean()
        v_clip = old_v + jnp.clip(v - old_v, -ppo["vf_clip"], ppo["vf_clip"])
        vl = 0.5 * jnp.maximum(jnp.square(v - tgt), jnp.square(v_clip - tgt)).mean()
        return pg + ppo["vf_coef"] * vl - ppo["ent_coef"] * _entropy(logits).mean()

    def update(key):
        key, k_net, k_reset = jax.random.split(key, 3)
        net0 = init_net(k_net, obs_dim, n_heads, n_levels, tuple(ppo["hidden"]))
        states = shard(ref.batch_reset(k_reset, tabs, n_envs))
        obs = shard(flat(ref.batch_observe(states, tabs, env_cfg)))

        def roll(carry, _):
            states, obs, key = carry
            key, k_act, k_env = jax.random.split(key, 3)
            logits, v = forward(net0, obs, n_heads, n_levels, mlp_dtype)
            act = jax.random.categorical(k_act, logits, axis=-1)
            lp = _log_prob(logits, act)
            states, r, done, info = ref.batch_autoreset_step(k_env, states, nest(act), tabs, env_cfg, n_envs)
            nobs = shard(flat(ref.batch_observe(states, tabs, env_cfg)))
            return (states, nobs, key), (flat(done), act, v, flat(r) * scale, lp, obs)

        (states, obs, key), (done, act, val, rew, lp, tobs) = jax.lax.scan(roll, (states, obs, key), None, T)
        _, last_v = forward(net0, obs, n_heads, n_levels, mlp_dtype)

        def gae_step(carry, x):
            g, nv = carry
            d, r, v = x
            delta = r + gamma * nv * (1 - d) - v
            g = delta + gamma * lam * (1 - d) * g
            return (g, v), g

        _, adv = jax.lax.scan(gae_step, (jnp.zeros_like(last_v), last_v), (done.astype(jnp.float32), rew, val), reverse=True)
        tgt = adv + val
        data = tuple(x.reshape((bs,) + x.shape[2:]) for x in (tobs, act, val, lp, adv, tgt))

        def epoch(carry, _):
            net, mu, nu, count, key = carry
            key, k_perm = jax.random.split(key)
            perm = jax.random.permutation(k_perm, bs)
            mbs = tuple(jnp.take(x, perm, axis=0).reshape((n_mb, -1) + x.shape[1:]) for x in data)

            def minibatch(c, mb):
                net, mu, nu, count = c
                loss, g = jax.value_and_grad(loss_fn)(net, *mb)
                gn = _global_norm(g)
                g = jax.tree_util.tree_map(lambda x: x * jnp.minimum(1.0, ppo["max_grad_norm"] / jnp.maximum(gn, 1e-9)), g)
                count = count + 1
                lr = ppo["lr"] * (1.0 - jnp.minimum(count.astype(jnp.float32) / total_opt_steps, 1.0)) if ppo["anneal_lr"] else ppo["lr"]
                mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
                nu = jax.tree_util.tree_map(lambda m, x: b2 * m + (1 - b2) * jnp.square(x), nu, g)
                c1 = 1 - b1 ** count.astype(jnp.float32)
                c2 = 1 - b2 ** count.astype(jnp.float32)
                net = jax.tree_util.tree_map(lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)), net, mu, nu)
                return (net, mu, nu, count), loss

            (net, mu, nu, count), loss = jax.lax.scan(minibatch, (net, mu, nu, count), mbs)
            return (net, mu, nu, count, key), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, net0)
        (net, mu, _, _, _), loss = jax.lax.scan(
            epoch, (net0, zeros, zeros, jnp.int32(0), key), None, epochs
        )
        return UpdateResult(net0, net, mu, {"loss": loss.mean()})

    return update
