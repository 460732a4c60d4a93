"""Fused Chargax station step — Pallas TPU kernel (DESIGN.md §6).

At 10^5-10^6 parallel environments the station transition is the RL
training-loop inner loop.  This kernel fuses action clipping, the Eq. 5 tree
constraint, and the charging integration into one VMEM-resident pass:

  grid = (n_envs / B_blk,)            # one grid step per env block

Per block, all pole-state slabs (B_blk, P) live in VMEM; the constraint check
is a static loop over the (tiny, padded) node axis, each node an f32 lane
reduction of one membership row against the (B_blk, P) currents; charging is
a fused elementwise epilogue.  The pole axis P is padded to a lane multiple
(128) and the node axis Nn to a sublane multiple (8) by ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.chargax_step.ref import BIG


def _chargax_kernel(
    # dynamic state slabs, all (B_blk, P)
    target_ref, occupied_ref, soc_ref, e_remain_ref, cap_ref, rbar_ref, tau_ref,
    grid_cap_ref,  # (B_blk, 128) feeder cap [kW], lane-replicated scalar
    # static params
    voltage_ref,  # (8, P) — row 0 real, sublane-padded
    imax_ref,  # (8, P)
    eff_ref,  # (8, P) storage efficiency (1 cars, eta_b battery)
    power_w_ref,  # (8, P) grid-side watts per charging amp (0 on padding)
    member_ref,  # (Nn, P) 0/1 node membership, one lane row per node
    node_budget_ref,  # (Nn, 128) per-node budget [A], lane-replicated
    # outputs, (B_blk, P) unless noted
    current_out, soc_out, e_remain_out, rhat_out, e_pole_out,
    excess_out,  # (B_blk, 128) lane-replicated scalar
    p_req_out,  # (B_blk, 128) lane-replicated scalar [kW]
    *,
    dt_hours: float,
    n_nodes: int,
):
    # parameter rows stay 2-D (1, P) so they broadcast along sublanes
    v = voltage_ref[0:1, :]
    imax = imax_ref[0:1, :]
    eff = eff_ref[0:1, :]

    soc = soc_ref[...]
    rbar = rbar_ref[...]
    tau = tau_ref[...]
    cap = cap_ref[...]
    e_remain = e_remain_ref[...]
    occ = occupied_ref[...]

    inv_tau = 1.0 / jnp.maximum(1.0 - tau, 1e-6)
    rhat_chg = jnp.where(soc <= tau, rbar, rbar * (1.0 - soc) * inv_tau)
    rhat_dis = jnp.where((1.0 - soc) <= tau, rbar, rbar * soc * inv_tau)

    amp_per_kwh = 1000.0 / jnp.maximum(v * dt_hours, 1e-9)
    req_amp = e_remain * amp_per_kwh
    up = jnp.minimum(
        jnp.minimum(rhat_chg, imax),
        jnp.minimum(
            req_amp,
            (1.0 - soc) * cap * amp_per_kwh / jnp.maximum(eff, 1e-9),
        ),
    )
    down = -jnp.minimum(
        jnp.minimum(rhat_dis, imax),
        soc * cap * eff * amp_per_kwh,
    )
    i = jnp.clip(target_ref[...], down, jnp.maximum(up, 0.0)) * occ

    # --- Eq. 5: per-node load as an f32 lane reduction on the VPU -----------
    # Static unroll over the tiny node axis.  Each node reads one (1, P)
    # membership row, so nothing is relaid out per env row; an MXU dot would
    # need HIGHEST precision to keep f32 loads and a (B, Nn) lane slice.
    abs_i = jnp.abs(i)
    scale = jnp.full_like(i, 1.0)
    excess = jnp.zeros((i.shape[0], 1), jnp.float32)
    for n in range(n_nodes):
        row = member_ref[n : n + 1, :]  # (1, P)
        budget = node_budget_ref[n : n + 1, 0:1]  # (1, 1)
        load = jnp.sum(abs_i * row, axis=-1, keepdims=True)  # (B, 1)
        s_node = jnp.minimum(1.0, budget / jnp.maximum(load, 1e-9))
        excess = jnp.maximum(excess, load - budget)
        scale = jnp.minimum(scale, jnp.where(row > 0, s_node, BIG))
    i = i * scale

    # --- feeder envelope (allocate stage, fused in) ---------------------------
    # Only charging amps draw grid power; unlimited cap -> gscale == 1.0,
    # a bitwise no-op, matching transition.allocate/curtail.
    pw = power_w_ref[0:1, :]
    p_req = jnp.sum(jnp.maximum(i, 0.0) * pw, axis=-1, keepdims=True) / 1000.0
    gscale = jnp.minimum(1.0, grid_cap_ref[:, 0:1] / jnp.maximum(p_req, 1e-9))
    i = jnp.where(i > 0.0, i * gscale, i)

    # --- charge epilogue ------------------------------------------------------
    e = v * i * dt_hours / 1000.0
    soc_delta = jnp.where(e >= 0, e * eff, e / jnp.maximum(eff, 1e-9))
    soc_new = jnp.clip(soc + soc_delta / jnp.maximum(cap, 1e-6), 0.0, 1.0)
    headroom = jnp.where(e_remain >= 0.5 * BIG, BIG, (1.0 - soc_new) * cap)
    # a current that reached the request bound zeroes the request
    # (transition.pole_integrate)
    e_rem_new = jnp.where(
        i >= req_amp, 0.0, jnp.minimum(jnp.maximum(e_remain - e, 0.0), headroom)
    )
    rhat_new = jnp.where(soc_new <= tau, rbar, rbar * (1.0 - soc_new) * inv_tau) * occ

    current_out[...] = i
    soc_out[...] = soc_new
    e_remain_out[...] = e_rem_new
    rhat_out[...] = rhat_new
    e_pole_out[...] = e
    excess_out[...] = jnp.broadcast_to(excess, excess_out.shape)
    p_req_out[...] = jnp.broadcast_to(p_req, p_req_out.shape)


def chargax_fused_step(
    slabs_arrays: tuple[jnp.ndarray, ...],  # 7 x (B, P) in PoleSlabs order
    params_arrays: tuple[jnp.ndarray, ...],  # voltage/imax/eff/power_w (8,P), member (Nn,P), budget (Nn,128)
    grid_cap: jnp.ndarray,  # (B, 128) feeder cap [kW], lane-replicated
    *,
    dt_hours: float,
    block_envs: int = 256,
    interpret: bool = False,
):
    b, p = slabs_arrays[0].shape
    nn = params_arrays[4].shape[0]
    assert b % block_envs == 0, (b, block_envs)

    grid = (b // block_envs,)
    state_spec = pl.BlockSpec((block_envs, p), lambda e: (e, 0))
    scalar_spec = pl.BlockSpec((block_envs, 128), lambda e: (e, 0))
    param_spec_row = pl.BlockSpec((8, p), lambda e: (0, 0))
    kernel = functools.partial(_chargax_kernel, dt_hours=dt_hours, n_nodes=nn)
    out_shapes = [jax.ShapeDtypeStruct((b, p), jnp.float32) for _ in range(5)]
    out_shapes += [jax.ShapeDtypeStruct((b, 128), jnp.float32)] * 2
    out_specs = [state_spec] * 5 + [scalar_spec] * 2

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[state_spec] * 7
        + [scalar_spec]
        + [param_spec_row] * 4
        + [
            pl.BlockSpec((nn, p), lambda e: (0, 0)),
            pl.BlockSpec((nn, 128), lambda e: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*slabs_arrays, grid_cap, *params_arrays)
