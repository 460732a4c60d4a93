"""Pure-jnp oracle for the fused Chargax station step (stages 1-2 of App. A.2).

Operates on a *unified pole representation*: the station battery is pole
index ``n_evse`` (the paper's "(N+1)-th charging pole"), with a per-pole
storage efficiency vector:

    cars:    eff = 1                       (port losses live in path_eff)
    battery: eff = eta_b                   (store eta*E, drain E/eta)

so one elementwise pipeline serves every pole.  The per-pole physics IS the
core staged pipeline's — :func:`repro.core.transition.pole_bounds` /
``pole_clip`` / ``pole_integrate`` are called directly, so kernel/core
parity is structural rather than a hand-kept duplicate; only the Eq. 5 tree
constraint is re-expressed here in its batched matmul form.
``fused_step_ref`` is the oracle the Pallas kernel must match within fp32
op-reorder tolerance.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.transition import (
    BIG,
    charge_rate,
    pole_bounds,
    pole_clip,
    pole_integrate,
)

__all__ = [
    "BIG",
    "PARITY_ATOL",
    "PARITY_RTOL",
    "PoleSlabs",
    "PoleParams",
    "FusedOut",
    "charge_rate",
    "fused_step_ref",
]

# fp32 op-reorder tolerance between the compiled kernel (or another
# backend) and the staged pipeline / this reference
PARITY_RTOL = 1e-4
PARITY_ATOL = 2e-4


class PoleSlabs(NamedTuple):
    """Per-pole dynamic state, all (..., P) float32 (P = padded poles)."""

    target: jnp.ndarray  # requested current [A], signed
    occupied: jnp.ndarray
    soc: jnp.ndarray
    e_remain: jnp.ndarray  # kWh (BIG for the battery)
    cap: jnp.ndarray  # kWh
    rbar: jnp.ndarray  # max current [A]
    tau: jnp.ndarray


class PoleParams(NamedTuple):
    """Static per-pole / per-node parameters (P-padded, node-padded)."""

    voltage: jnp.ndarray  # (P,)
    imax: jnp.ndarray  # (P,)
    eff: jnp.ndarray  # (P,) storage efficiency: 1 for cars, eta_b battery
    member: jnp.ndarray  # (Nn, P) 0/1
    node_budget: jnp.ndarray  # (Nn,)  BIG on padding rows
    power_w: jnp.ndarray  # (P,) grid-side watts per charging amp:
    #     evse_voltage/path_eff for EVSE lanes, batt_voltage for the battery
    #     lane, 0 on padding — so p_req = sum(max(i,0) * power_w) / 1000 [kW]


class FusedOut(NamedTuple):
    current: jnp.ndarray  # (..., P) post-constraint amps
    soc: jnp.ndarray
    e_remain: jnp.ndarray
    rhat: jnp.ndarray
    e_pole: jnp.ndarray  # (..., P) kWh delivered (signed, pole-side)
    excess: jnp.ndarray  # (...,) max node violation pre-rescale [A]
    p_req: jnp.ndarray  # (...,) requested grid power [kW] pre-curtail


def fused_step_ref(
    slabs: PoleSlabs,
    pp: PoleParams,
    dt_hours: float,
    cap_kw: jnp.ndarray | None = None,
) -> FusedOut:
    # --- per-pole clips: the core pipeline's shared physics -----------------
    up, down = pole_bounds(
        slabs.soc,
        slabs.e_remain,
        slabs.cap,
        slabs.rbar,
        slabs.tau,
        pp.voltage,
        pp.imax,
        pp.eff,
        dt_hours,
    )
    i = pole_clip(slabs.target, up, down, slabs.occupied)

    # --- Eq. 5 tree constraints (batched matmul form of the core's
    # constraint_scale, f32 at HIGHEST like the core's matvec) ---------------
    load = jnp.matmul(
        jnp.abs(i), pp.member.T, precision=jax.lax.Precision.HIGHEST
    )  # (..., Nn)
    s_node = jnp.minimum(1.0, pp.node_budget / jnp.maximum(load, 1e-9))
    excess = jnp.max(jnp.maximum(load - pp.node_budget, 0.0), axis=-1)
    scale = jnp.full_like(i, 1.0)
    for n in range(pp.member.shape[0]):  # static, tiny node count
        scale = jnp.minimum(
            scale, jnp.where(pp.member[n] > 0, s_node[..., n : n + 1], BIG)
        )
    i = i * scale

    # --- feeder envelope (core's allocate stage, folded in) -----------------
    # Only *charging* amps draw grid power; an unlimited cap lowers to
    # scale == 1.0, a bitwise no-op (matching transition.allocate/curtail).
    p_req = jnp.sum(jnp.maximum(i, 0.0) * pp.power_w, axis=-1) / 1000.0
    if cap_kw is not None:
        gscale = jnp.minimum(1.0, cap_kw / jnp.maximum(p_req, 1e-9))
        i = jnp.where(i > 0.0, i * gscale[..., None], i)

    # --- charge over dt (shared integrator) ---------------------------------
    e, soc, e_remain, rhat = pole_integrate(
        slabs.soc,
        slabs.e_remain,
        slabs.cap,
        slabs.rbar,
        slabs.tau,
        slabs.occupied,
        pp.voltage,
        i,
        pp.eff,
        dt_hours,
    )
    return FusedOut(i, soc, e_remain, rhat, e, excess, p_req)
