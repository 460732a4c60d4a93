"""The four-chip PPO cell's data-parallel path at a tiny size on four CPU
devices (a child process, since the device count is fixed when JAX
starts): ``run.run_cell`` is correct against the sharded reference, the
compiled program divides the env batch, and its losses equal the one-device
program's within the cell's limits.  Also: the reference's ``shard`` hook
changes nothing on one device."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests.tiny import on_four_devices, tiny_cell

WORKLOAD = "ppo.paper16_shop_dp4"


@pytest.fixture(scope="module")
def four_and_one():
    return on_four_devices(WORKLOAD, [(4, None), (1, None)])


def test_four_devices_correct_and_divided(four_and_one):
    four = four_and_one[0]
    assert four["rc"] == 0
    line = four["line"]
    assert line["correct"] is True and line["device"]["count"] == 4
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    # run_cell's set-up raised had the batch not been divided; read it again
    assert four["undivided"] == []


def test_one_device_program_holds_the_batch_whole(four_and_one):
    """The divided-batch check catches a program that runs whole on one chip."""
    one = four_and_one[1]
    assert one["line"]["correct"] is True and one["line"]["device"]["count"] == 1
    # (its [steps, rows, obs] check is moot here: at this size the nested
    # observations [scenarios, envs per scenario, obs] have the same shape)
    assert [w.split(":")[0] for w in one["undivided"] if not w.startswith("rollout")] == ["observations", "env state"]


def test_four_device_loss_matches_one_device(four_and_one):
    from bench.harness import compare

    limits = tiny_cell(WORKLOAD).limits
    four, one = four_and_one
    assert len(four["losses"]) == len(one["losses"]) == 3
    for a, b in zip(four["losses"], one["losses"]):
        assert compare.rel_gap(a, b, 0.1) <= limits["loss_gap"]


def test_reference_shard_hook_changes_nothing_on_one_device():
    import jax
    import jax.numpy as jnp

    from bench.harness import tables
    from bench.harness.drivers import shard_leading
    from bench.reference import ppo_ref
    from repro.launch.mesh import make_single_device_mesh

    config = tiny_cell("ppo.paper16_shop").config
    _, params = tables.build(config)
    tabs = {k: jnp.asarray(v) for k, v in tables.as_dict(params).items()}
    key = jax.random.key(2**31 + 5)
    plain = ppo_ref.make_update(tabs, config)
    ident = ppo_ref.make_update(tabs, config, shard=lambda tree: tree)
    assert str(jax.make_jaxpr(plain)(key)) == str(jax.make_jaxpr(ident)(key))
    mesh = make_single_device_mesh()
    with jax.sharding.set_mesh(mesh):
        sharded = jax.device_get(jax.jit(ppo_ref.make_update(tabs, config, shard=shard_leading(mesh)))(key))
    want = jax.device_get(jax.jit(plain)(key))
    for a, b in zip(jax.tree_util.tree_leaves(sharded), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
