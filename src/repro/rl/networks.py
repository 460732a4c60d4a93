"""Actor-critic networks for Chargax PPO (paper App. B: standard PureJaxRL MLP).

Functional, flax-free: parameters are nested dicts of jnp arrays.  The policy
head is a *factorized categorical* — one (2D+1)-way categorical per charging
pole plus one for the battery (paper: discretisation level 10 per port).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


def orthogonal(key: jax.Array, shape: tuple[int, int], scale: float) -> jnp.ndarray:
    """Orthogonal init (the PPO-standard initialisation)."""
    n_rows, n_cols = shape
    big = max(n_rows, n_cols)
    a = jax.random.normal(key, (big, big), jnp.float32)
    q, r = jnp.linalg.qr(a)
    q = q * jnp.sign(jnp.diag(r))[None, :]
    return scale * q[:n_rows, :n_cols]


def dense_init(key, in_dim, out_dim, scale=math.sqrt(2.0)):
    return {
        "w": orthogonal(key, (in_dim, out_dim), scale),
        "b": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(p, x):
    return x @ p["w"] + p["b"]


class PolicyOutput(NamedTuple):
    logits: jnp.ndarray  # (..., n_heads, n_actions)
    value: jnp.ndarray  # (...,)


def init_actor_critic(
    key: jax.Array,
    obs_dim: int,
    n_heads: int,
    n_actions: int,
    hidden: tuple[int, ...] = (128, 128),
) -> dict:
    keys = jax.random.split(key, 2 * len(hidden) + 2)
    params: dict = {"actor": {}, "critic": {}}
    d = obs_dim
    for i, h in enumerate(hidden):
        params["actor"][f"h{i}"] = dense_init(keys[2 * i], d, h)
        params["critic"][f"h{i}"] = dense_init(keys[2 * i + 1], d, h)
        d = h
    params["actor"]["out"] = dense_init(keys[-2], d, n_heads * n_actions, scale=0.01)
    params["critic"]["out"] = dense_init(keys[-1], d, 1, scale=1.0)
    return params


def apply_actor_critic(
    params: dict, obs: jnp.ndarray, n_heads: int, n_actions: int
) -> PolicyOutput:
    n_hidden = sum(1 for k in params["actor"] if k.startswith("h"))
    xa = xc = obs
    for i in range(n_hidden):
        xa = jnp.tanh(dense(params["actor"][f"h{i}"], xa))
        xc = jnp.tanh(dense(params["critic"][f"h{i}"], xc))
    flat_logits = dense(params["actor"]["out"], xa)
    logits = flat_logits.reshape(*obs.shape[:-1], n_heads, n_actions)
    value = dense(params["critic"]["out"], xc)[..., 0]
    return PolicyOutput(logits, value)


# ---------------------------------------------------------------------------
# Factorized categorical distribution helpers
# ---------------------------------------------------------------------------
def sample_action(key: jax.Array, logits: jnp.ndarray) -> jnp.ndarray:
    """(..., H, K) logits -> (..., H) int32 actions."""
    return jax.random.categorical(key, logits, axis=-1)


def log_prob(logits: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
    """Joint log-probability, summed over heads.

    Each head's level is picked by compare-and-select over the K levels, not
    by ``take_along_axis``: on a TPU the gather that the latter lowers to,
    over logits fresh from the output matmul, costs far more than the
    O(K) select.  The sum of one selected value and K-1 exact zeros is that
    value, so the result and its gradient are the gather's bit for bit.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    hit = jnp.arange(logits.shape[-1], dtype=action.dtype) == action[..., None]
    picked = jnp.where(hit, logp, 0.0).sum(axis=-1)
    return picked.sum(axis=-1)


def entropy(logits: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -(jnp.exp(logp) * logp).sum(axis=-1).sum(axis=-1)
