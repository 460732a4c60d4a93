"""Work counts from shapes: the MLP's FLOPs per PPO update and the bytes one
env step has to move, the same whichever implementation runs it.

Both are counted from the configuration and the reference's logical shapes,
never from the program's compiled code, so a change to the program cannot
change them.
"""
from __future__ import annotations

import numpy as np


def station_shapes(config: dict, tables: dict) -> dict:
    n_evse = int(np.asarray(tables["evse_voltage"]).shape[-1])
    n_nodes = int(np.asarray(tables["member"]).shape[-2])
    n_models = int(np.asarray(tables["car_capacity"]).shape[-1])
    disc = int(config["env"]["discretization"])
    return {
        "n_evse": n_evse,
        "n_nodes": n_nodes,
        "n_models": n_models,
        "n_heads": n_evse + 1,
        "n_levels": 2 * disc + 1,
        "obs_dim": 8 * n_evse + 2 + 4 + 3,
    }


def mlp_forward_flops(obs_dim: int, hidden: tuple[int, ...], n_out_actor: int) -> int:
    """Matmul FLOPs (2 per multiply-add) of one actor-critic forward for one
    sample; biases and tanh are left out."""
    flops = 0
    for out in (n_out_actor, 1):
        d = obs_dim
        for h in hidden:
            flops += 2 * d * h
            d = h
        flops += 2 * d * out
    return flops


def ppo_update_flops(config: dict, shapes: dict) -> int:
    """MLP FLOPs of one PPO update: a forward per rollout sample, then per
    epoch a forward and a backward (twice the forward) per sample."""
    ppo = config["ppo"]
    f = mlp_forward_flops(shapes["obs_dim"], tuple(ppo["hidden"]), shapes["n_heads"] * shapes["n_levels"])
    samples = config["num_envs"] * ppo["rollout_steps"]
    return samples * f * (1 + 3 * ppo["update_epochs"])


def env_step_bytes(config: dict, tables: dict) -> int:
    """Least bytes one env step moves: the station state read and written,
    the action read, the parameter rows the step reads and the observation
    written.  Shapes are the reference's logical ones (no padded lanes)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import chargax_ref as ref

    one = {k: jax.ShapeDtypeStruct(np.asarray(v).shape[1:], np.asarray(v).dtype) for k, v in tables.items()}
    state = jax.eval_shape(lambda k, t: ref.reset(k, t), jax.random.key(0), one)
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state))
    sh = station_shapes(config, tables)
    n, m = sh["n_evse"], sh["n_models"]
    f32 = 4
    action = sh["n_heads"] * jnp.dtype(jnp.int32).itemsize
    obs = sh["obs_dim"] * f32
    # per-step parameter rows: the station (per-port vectors, the tree and its
    # budgets), the car table and the day's mix, and one entry of each
    # per-(day, step) table (arrival rate, day scale, PV, feeder cap, setpoint)
    per_port = 6 * n * f32
    tree = sh["n_nodes"] * (n + 1) * f32 + sh["n_nodes"] * f32
    cars = 5 * m * f32
    scalars = sum(1 for k, v in one.items() if v.shape == ()) * f32
    rows = per_port + tree + cars + 5 * f32 + scalars
    return int(2 * state_bytes + action + obs + rows)


def env_step_ops(config: dict, tables: dict) -> int:
    """Arithmetic of one env step that any implementation has to do, counted
    as the Eq. 5 tree reduction (a multiply-add per node and leaf); the
    elementwise work is of the same order and far below the byte bound."""
    sh = station_shapes(config, tables)
    return 2 * sh["n_nodes"] * (sh["n_evse"] + 1)
