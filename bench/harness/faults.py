"""Faults planted under the timed path, to show that ``correct`` catches
them: a step that leaves its state unchanged, half of the batch left out
(the mean taken over the rest), an answer altered where it is made, and,
on several chips, the exchange between chips left out.

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


def no_exchange(driver: str):
    """Each chip takes the loss and gradient of its own rows of the
    minibatch and never sums them with the other chips' (data-parallel PPO
    over the mesh set by ``set_mesh``; a cell on one chip has no exchange)."""
    if driver != "ppo_update":
        raise ValueError(f"no exchange between chips to leave out in {driver!r}")
    import jax
    from jax.sharding import PartitionSpec

    from repro.rl import ppo

    def value_and_grad(fn, **kw):
        both = jax.value_and_grad(fn, **kw)

        def local(params, *batch):
            specs = (PartitionSpec(),) + (PartitionSpec("data"),) * len(batch)
            return jax.shard_map(both, in_specs=specs, out_specs=PartitionSpec(), check_vma=False)(params, *batch)

        return local

    return _patched(ppo, "jax", _Proxy(jax, value_and_grad=value_and_grad))


FAULTS = {
    "unchanged_state": unchanged_state,
    "half_batch": half_batch,
    "altered_answer": altered_answer,
    "no_exchange": no_exchange,
}
