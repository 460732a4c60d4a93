"""Production meshes (assignment spec).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then builds these meshes from host placeholder devices.
"""
from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding by constraint)."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU integration tests (8 forced host devices)."""
    return _make_mesh(shape, axes)


def make_single_device_mesh():
    return _make_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_model: int = 1):
    """Host-count-aware mesh over ALL visible devices: data axis = device
    count // n_model.  This is the mesh env-batch sharding wants — fleet
    stations / PPO envs over 'data', nothing over 'model' — and it adapts to
    however many devices the process sees (1 CPU, N forced host devices,
    a real multi-chip slice).
    """
    n_dev = jax.device_count()
    if n_dev % n_model:
        raise ValueError(f"device count {n_dev} not divisible by n_model={n_model}")
    return _make_mesh((n_dev // n_model, n_model), ("data", "model"))
