"""Faults planted under the timed path, to show that ``correct`` catches
them: a step that leaves its state unchanged, half of the batch left out
(the mean taken over the rest), and an answer altered where it is made.

Each is a context manager that patches the system's modules (never the
reference's), so a driver built inside it runs the broken program.
"""
from __future__ import annotations

import contextlib
import types

# what an altered answer adds to every env step's reward [EUR]
REWARD_NUDGE = 0.01


class _Proxy(types.SimpleNamespace):
    """A module stand-in: the given attributes, everything else from ``base``."""

    def __init__(self, base, **attrs):
        super().__init__(**attrs)
        self._base = base

    def __getattr__(self, name):
        return getattr(self._base, name)


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _env_step_patch(edit):
    """Patch ``ChargaxEnv.step`` so its TimeStep goes through ``edit(state, ts)``."""
    from repro.core.env import ChargaxEnv

    orig = ChargaxEnv.step

    def step(self, key, state, action, params=None):
        return edit(state, orig(self, key, state, action, params))

    return _patched(ChargaxEnv, "step", step)


def unchanged_state(driver: str):
    if driver == "ppo_update":
        from repro.rl import ppo

        return _patched(ppo, "apply_updates", lambda params, updates: params)
    return _env_step_patch(lambda state, ts: ts._replace(state=state))


def half_batch(driver: str):
    if driver == "ppo_update":
        import jax.numpy as jnp

        from repro.rl import ppo

        def take(x, idx, axis=0):
            half = idx[: idx.shape[0] // 2]
            return jnp.take(x, jnp.concatenate([half, half]), axis=axis)

        return _patched(ppo, "jnp", _Proxy(jnp, take=take))

    import jax
    import jax.numpy as jnp

    from repro.envs import VmapWrapper

    orig = VmapWrapper.step

    def step(self, key, state, action, params=None):
        ts = orig(self, key, state, action, params)
        n = self.num_envs // 2

        def keep_half(new, old):
            return jnp.concatenate([new[:n], old[n:]]) if getattr(new, "ndim", 0) and new.shape[0] == self.num_envs else new

        return ts._replace(state=jax.tree_util.tree_map(keep_half, ts.state, state))

    return _patched(VmapWrapper, "step", step)


def altered_answer(driver: str):
    return _env_step_patch(lambda state, ts: ts._replace(reward=ts.reward + REWARD_NUDGE))


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch, "altered_answer": altered_answer}
