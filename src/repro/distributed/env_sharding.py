"""Environment-batch sharding: place the env/station axis over the mesh.

``FleetEnv`` rollouts and the PPO environment batch carry a leading
environment (or station) axis.  At pod scale that axis shards over the
mesh's data axes (``('pod', 'data')`` when present) so rollouts parallelise
across chips without host transfers — the paper's on-device-rollout claim
generalised to meshes (DESIGN.md §3).  On a single device every helper here
degrades to the identity, so the same env/PPO code compiles unchanged in
CPU tests.

Two flavours:

* **ambient** — :func:`constrain_env_batch` annotates the leading axis of
  every leaf against the mesh installed by ``jax.sharding.set_mesh`` and is a
  no-op when none is active.  Env code (``FleetEnv``, ``make_train``) calls
  it unconditionally.
* **explicit** — :func:`make_shard_envs` / :func:`place_env_batch` build
  ``NamedSharding``s for a concrete mesh (launch scripts, benchmarks), with
  per-leaf divisibility fallback to replication so every mesh shape
  compiles.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding


def constrain_env_batch(tree: Any) -> Any:
    """Constrain the leading (env/station) axis of every leaf to the data axes.

    Ambient-mesh flavour of :func:`sharding.constrain`: a no-op without an
    active mesh or when the leading dim does not divide the data-axis size,
    so callers annotate unconditionally (single-device fallback).
    """
    return jax.tree_util.tree_map(lambda x: sharding.constrain(x, sharding.DP), tree)


def env_shardings(tree: Any, mesh: Mesh) -> Any:
    """Pytree of ``NamedSharding``s sharding each leaf's leading axis.

    Leaves whose leading dim does not divide the data-axis size (or scalars)
    are replicated — every fleet composition places on every mesh.
    """
    axes = sharding.data_axes(mesh)
    size = 1
    for a in axes:
        size *= mesh.shape[a]

    def one(x):
        shape = getattr(x, "shape", ())
        if not axes or size <= 1 or not shape or shape[0] % size:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))

    return jax.tree_util.tree_map(one, tree)


def place_env_batch(tree: Any, mesh: Mesh) -> Any:
    """``device_put`` a stacked env/fleet pytree onto the mesh's data axes."""
    return jax.tree_util.tree_map(
        jax.device_put, tree, env_shardings(tree, mesh)
    )


def make_shard_envs(mesh: Mesh):
    """Explicit-mesh constraint callable for ``make_train(shard_envs=...)``.

    Returns a function mapping an array (or pytree) to the same values with
    the leading env axis constrained onto ``mesh``'s data axes.
    """
    def shard(tree):
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, env_shardings(tree, mesh)
        )

    return shard
