"""Chargax staged transition pipeline (paper §4 "Transition Function", App. A.2).

The step is a sequence of individually-jittable pure stages::

    decode -> request -> allocate -> deliver -> depart_arrive -> settle
           -> advance_time -> observe

  decode        — map the discrete factorized action to target amps
                  (direct and the paper's additive/delta form),
  request       — clip targets by car curve, port limits and pack headroom,
                  then enforce the Eq. 5 tree constraints (``apply_actions``),
  allocate      — curtail the station's *grid-side* charging power against
                  the feeder/transformer envelope (``grid_cap_kw_table``);
                  with the default unlimited cap this stage is an exact
                  bitwise no-op, so non-grid scenarios are unchanged,
  deliver       — integrate energy over dt (``charge_cars``),
  depart_arrive — deadline / request-met departures, Poisson arrivals,
  settle        — energy bookkeeping, Eq. 1-3 reward, V2G debt settlement,
                  plus the grid-axis penalties (cap violation, setpoint
                  tracking error),
  advance_time  — clock tick + midnight calendar rollover,
  observe       — flat observation vector.

``ChargaxEnv.step`` is pure composition of these stages, and the fused
Pallas oracle (``repro/kernels/chargax_step/ref.py``) calls the *same*
per-pole physics helpers (``pole_bounds`` / ``pole_clip`` /
``pole_integrate``) — kernel/core parity is structural, not duplicated.
The helpers treat the station battery as the paper's (N+1)-th pole: a lane
with ``eff = eta_b`` and an unbounded energy request (``BIG`` sentinel).

Fleet grid coupling reuses the same seam: ``FleetEnv`` with
``couple_grid=True`` runs the vmapped ``request`` stage, applies one shared
proportional ``curtail`` against the fleet feeder cap, and resumes with the
vmapped ``deliver``-onward stages — all pure array ops, so the one-jit-entry
invariant over the whole scenario catalog survives.
"""
from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.rewards import PenaltyTerms, StepEnergies, compute_reward, step_energies
from repro.core.state import EnvParams, EnvState
from repro.obs import annotate
from repro.utils import replace

# Energy-request sentinel for poles with no finite request (the station
# battery): large enough that the request never binds, small enough that
# `BIG * 1000 / (V dt)` stays finite in fp32.
BIG = 1e30

# Default feeder cap [kW]: far above any station's worst-case draw, so the
# allocate stage lowers to `scale == 1.0` exactly and curtailment is a
# bitwise no-op (x * 1.0 is exact in IEEE-754).
GRID_CAP_UNLIMITED = 1e9

# A charge-sensitive car leaves once at most this much of its request remains
# [kWh].  A step whose current reached the request bound zeroes the request
# outright (`pole_integrate`), so the threshold only decides requests that
# decay under the charge curve.
CHARGED_KWH = 1e-6


# ---------------------------------------------------------------------------
# Charging curve (Appendix A: piece-wise linear; discharge = vertical flip
# of the charge curve at SoC = 0.5)
# ---------------------------------------------------------------------------
def charge_rate(soc: jnp.ndarray, rbar: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """r_hat_{tau, rbar}(SoC): max charge current at the given state of charge."""
    return jnp.where(soc <= tau, rbar, rbar * (1.0 - soc) / jnp.maximum(1.0 - tau, 1e-6))


def discharge_rate(soc: jnp.ndarray, rbar: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Discharge limit: the charge curve flipped at SoC=0.5 (paper App. A.1)."""
    return charge_rate(1.0 - soc, rbar, tau)


# ---------------------------------------------------------------------------
# Shared per-pole physics (cars AND the battery pole; also the fused-kernel
# oracle) — `eff` is the pole's storage efficiency: 1.0 for cars (port losses
# live in path_eff), eta_b for the battery (charging stores eta*E,
# discharging drains E/eta).
# ---------------------------------------------------------------------------
def request_amps(
    e_remain: jnp.ndarray, voltage: jnp.ndarray, dt_hours: float
) -> jnp.ndarray:
    """Current [A] that delivers the remaining request ``e_remain`` in one step."""
    return e_remain * 1000.0 / jnp.maximum(voltage * dt_hours, 1e-9)


def pole_bounds(
    soc: jnp.ndarray,
    e_remain: jnp.ndarray,
    cap: jnp.ndarray,
    rbar: jnp.ndarray,
    tau: jnp.ndarray,
    voltage: jnp.ndarray,
    imax: jnp.ndarray,
    eff: jnp.ndarray | float,
    dt_hours: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-pole current bounds [A]: (up >= 0 charge limit, down <= 0 discharge).

    Charge is limited by the car curve, the port, the remaining request and
    the pack headroom; discharge by the flipped curve and the pack content.
    ``e_remain = BIG`` disables the request bound (battery pole).
    """
    rhat_chg = charge_rate(soc, rbar, tau)
    rhat_dis = discharge_rate(soc, rbar, tau)
    max_chg_amp_req = request_amps(e_remain, voltage, dt_hours)
    max_chg_amp_soc = (
        (1.0 - soc) * cap * 1000.0 / jnp.maximum(voltage * dt_hours * eff, 1e-9)
    )
    max_dis_amp_soc = soc * cap * eff * 1000.0 / jnp.maximum(voltage * dt_hours, 1e-9)
    up = jnp.minimum(
        jnp.minimum(rhat_chg, imax),
        jnp.minimum(max_chg_amp_req, max_chg_amp_soc),
    )
    down = -jnp.minimum(jnp.minimum(rhat_dis, imax), max_dis_amp_soc)
    return up, down


def pole_clip(
    target: jnp.ndarray,
    up: jnp.ndarray,
    down: jnp.ndarray,
    occupied: jnp.ndarray | float,
) -> jnp.ndarray:
    """Clip a target current into [down, max(up, 0)]; empty poles draw nothing."""
    return jnp.clip(target, down, jnp.maximum(up, 0.0)) * occupied


def pole_integrate(
    soc: jnp.ndarray,
    e_remain: jnp.ndarray,
    cap: jnp.ndarray,
    rbar: jnp.ndarray,
    tau: jnp.ndarray,
    occupied: jnp.ndarray | float,
    voltage: jnp.ndarray,
    current: jnp.ndarray,
    eff: jnp.ndarray | float,
    dt_hours: float,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Integrate one pole over dt: (e_kwh, soc', e_remain', rhat').

    The remaining request grows when a pole is discharged (V2G) but never
    past the pack headroom ``(1 - SoC') * cap`` — an uncapped request would
    be unfillable energy poisoning the missing_kwh satisfaction penalty.
    Poles carrying the ``BIG`` request sentinel (battery) keep it.

    A current that reached the request bound of :func:`pole_bounds` delivered
    the whole request, which is then zeroed: ``e_remain - e`` would leave a
    residual of a few rounding ulps, and the charged-departure test would
    hinge on the last bit of a division, which differs between backends.
    """
    e = voltage * current * dt_hours / 1000.0  # kWh, pole-side
    soc_delta = jnp.where(e >= 0, e * eff, e / eff)
    soc_new = jnp.clip(soc + soc_delta / jnp.maximum(cap, 1e-6), 0.0, 1.0)
    headroom = jnp.where(e_remain >= 0.5 * BIG, BIG, (1.0 - soc_new) * cap)
    request_met = current >= request_amps(e_remain, voltage, dt_hours)
    e_remain_new = jnp.where(
        request_met, 0.0, jnp.minimum(jnp.maximum(e_remain - e, 0.0), headroom)
    )
    rhat_new = charge_rate(soc_new, rbar, tau) * occupied
    return e, soc_new, e_remain_new, rhat_new


# ---------------------------------------------------------------------------
# Stage: decode — discrete factorized action -> target amps
# ---------------------------------------------------------------------------
def decode_action(
    action: jnp.ndarray,
    discretization: int,
    allow_v2g: bool,
    evse_max_current: jnp.ndarray,
    batt_max_current: jnp.ndarray,
    v2g_mask: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Map a discrete factorized action (N+1,) int32 in [0, 2D] to target amps.

    Level k maps to ((k - D)/D) * I_max: the paper's "10%, 20%, ... up to 100%"
    discretisation, extended symmetrically for discharging.  Ports without V2G
    clip negative targets to 0 (the battery head always may discharge).  When
    V2G is on, ``v2g_mask`` (``EnvParams.evse_v2g_mask``) marks which ports
    have bidirectional hardware — the rest stay charge-only, so a scenario can
    lower any port fraction without a new compilation.
    """
    d = float(discretization)
    frac = (action.astype(jnp.float32) - d) / d  # [-1, 1]
    port_frac, batt_frac = frac[:-1], frac[-1]
    if not allow_v2g:
        port_frac = jnp.maximum(port_frac, 0.0)
    elif v2g_mask is not None:
        port_frac = jnp.where(
            v2g_mask > 0.5, port_frac, jnp.maximum(port_frac, 0.0)
        )
    return port_frac * evse_max_current, batt_frac * batt_max_current


def decode(
    params: EnvParams,
    state: EnvState,
    action: jnp.ndarray,
    *,
    discretization: int,
    allow_v2g: bool,
    action_mode: str = "direct",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decode stage: both action modes, as target amps (tgt_evse, tgt_batt).

    ``direct`` maps levels straight to amps; ``delta`` (the paper's additive
    form) maps levels to signed current *changes* applied on top of the
    currents held last step.
    """
    if action_mode == "direct":
        return decode_action(
            action,
            discretization,
            allow_v2g,
            params.evse_max_current,
            params.batt_max_current,
            v2g_mask=params.evse_v2g_mask,
        )
    if action_mode == "delta":
        d_evse, d_batt = decode_action(
            action,
            discretization,
            True,  # deltas may be negative even without v2g...
            params.evse_max_current,
            params.batt_max_current,
        )
        tgt_evse = state.evse_current + d_evse
        if not allow_v2g:
            tgt_evse = jnp.maximum(tgt_evse, 0.0)  # ...but targets may not
        else:  # charge-only hardware never targets negative amps
            tgt_evse = jnp.where(
                params.evse_v2g_mask > 0.5, tgt_evse, jnp.maximum(tgt_evse, 0.0)
            )
        return tgt_evse, state.batt_current + d_batt
    raise ValueError(f"unknown action_mode {action_mode!r}")


# ---------------------------------------------------------------------------
# Stage: request — apply targets + Eq. 5 constraint enforcement
# ---------------------------------------------------------------------------
class AppliedActions(NamedTuple):
    evse_current: jnp.ndarray  # (N,) post-constraint signed amps
    batt_current: jnp.ndarray  # ()
    constraint_excess: jnp.ndarray  # () max pre-rescale node violation [A]


def constraint_scale(
    currents: jnp.ndarray,  # (n_leaves,) signed amps (EVSEs + battery column)
    member: jnp.ndarray,  # (n_nodes, n_leaves)
    node_budget: jnp.ndarray,  # (n_nodes,) eta_H * I_H
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-leaf multiplicative scale enforcing Eq. 5 on every subtree.

    We use the conservative cable-thermal reading of Eq. 5 — each node carries
    the sum of *magnitudes* of its subtree currents (DESIGN.md §7).  With
    ``scale_j = min_{H ∋ j} s_H`` and ``s_H = budget_H / load_H`` the invariant
    ``sum_j |I_j * scale_j| <= budget_H`` holds for every node H, which the
    hypothesis tests assert.

    Returns (per-leaf scale in (0, 1], max pre-rescale node excess in amps).
    """
    # HIGHEST: a default-precision f32 dot may run in bf16 passes on TPU,
    # which would shift node loads by ~0.1% and curtail different currents
    load = jnp.matmul(
        member, jnp.abs(currents), precision=jax.lax.Precision.HIGHEST
    )  # (n_nodes,)
    s_node = jnp.minimum(1.0, node_budget / jnp.maximum(load, 1e-9))
    excess = jnp.max(jnp.maximum(load - node_budget, 0.0))
    # min over ancestors; a leaf with no constrained ancestor is unscaled
    per_leaf = jnp.where(member > 0, s_node[:, None], jnp.inf)
    scale = jnp.min(per_leaf, axis=0)
    return jnp.where(jnp.isfinite(scale), scale, 1.0), excess


def apply_actions(
    params: EnvParams,
    state: EnvState,
    target_evse: jnp.ndarray,  # (N,) requested amps (signed)
    target_batt: jnp.ndarray,  # () requested amps (signed)
    dt_hours: float,
) -> AppliedActions:
    # --- per-port physical clips (shared pole physics; eff=1 for cars) ------
    up, down = pole_bounds(
        state.soc,
        state.e_remain,
        state.cap,
        state.rbar,
        state.tau,
        params.evse_voltage,
        params.evse_max_current,
        1.0,
        dt_hours,
    )
    i_evse = pole_clip(target_evse, up, down, state.occupied)

    # --- battery clips: the (N+1)-th pole, eff=eta_b, unbounded request -----
    b_up, b_down = pole_bounds(
        state.batt_soc,
        jnp.float32(BIG),
        params.batt_capacity,
        params.batt_max_current,
        params.batt_tau,
        params.batt_voltage,
        params.batt_max_current,
        params.batt_eff,
        dt_hours,
    )
    i_batt = pole_clip(target_batt, b_up, b_down, 1.0)

    # --- Eq. 5 tree constraints (battery = extra leaf on the root) ----------
    leaf_currents = jnp.concatenate([i_evse, i_batt[None]])
    scale, excess = constraint_scale(leaf_currents, params.member, params.node_budget)
    leaf_currents = leaf_currents * scale
    return AppliedActions(leaf_currents[:-1], leaf_currents[-1], excess)


# `request` is the stage name in the pipeline; `apply_actions` the historical
# one — both resolve to the same function.
request = apply_actions


# ---------------------------------------------------------------------------
# Stage: allocate — grid power envelope (feeder/transformer coupling)
# ---------------------------------------------------------------------------
class AllocationResult(NamedTuple):
    applied: AppliedActions  # post-curtailment currents
    power_req_kw: jnp.ndarray  # () gross grid-side charging power requested
    power_kw: jnp.ndarray  # () post-curtailment grid draw
    cap_kw: jnp.ndarray  # () feeder cap in force this step
    violation_kw: jnp.ndarray  # () max(requested - cap, 0): the pre-curtail
    #     overshoot — the penalty the RL agent can drive to 0 by requesting
    #     less, and exactly the power the allocate stage had to shed


def requested_power_kw(params: EnvParams, applied: AppliedActions) -> jnp.ndarray:
    """Gross grid-side charging power [kW] of one station's applied currents.

    Conservative cable/transformer reading: charging draws count at the grid
    side (inflated by the port path efficiency); discharge (V2G / battery)
    does not offset them — a feeder is certified for gross draw, and netting
    would let simultaneous charge+discharge hide load behind the cap.
    """
    p_evse = jnp.sum(
        params.evse_voltage
        * jnp.maximum(applied.evse_current, 0.0)
        / params.evse_path_eff
    )
    p_batt = params.batt_voltage * jnp.maximum(applied.batt_current, 0.0)
    return (p_evse + p_batt) / 1000.0


def grid_cap_kw(params: EnvParams, state: EnvState) -> jnp.ndarray:
    """Feeder power cap [kW] in force at the state's (day, step)."""
    table = params.grid_cap_kw_table
    return table[jnp.mod(state.day, table.shape[0]), jnp.mod(state.t, table.shape[1])]


def curtail(applied: AppliedActions, scale: jnp.ndarray) -> AppliedActions:
    """Scale all *charging* currents by ``scale`` (discharge untouched).

    Scaling charging magnitudes down can only lower every Eq. 5 node load,
    so constrained currents stay feasible; ``scale == 1.0`` is a bitwise
    no-op (x * 1.0 is exact).
    """
    i_evse = jnp.where(
        applied.evse_current > 0.0, applied.evse_current * scale, applied.evse_current
    )
    i_batt = jnp.where(
        applied.batt_current > 0.0, applied.batt_current * scale, applied.batt_current
    )
    return AppliedActions(i_evse, i_batt, applied.constraint_excess)


def allocate(
    params: EnvParams,
    state: EnvState,
    applied: AppliedActions,
    cap_kw: jnp.ndarray | None = None,
) -> AllocationResult:
    """Proportionally curtail charging against the feeder power envelope.

    ``cap_kw`` overrides the per-station table lookup (the fleet coupled
    step passes the shared feeder cap).  With the default
    ``GRID_CAP_UNLIMITED`` table the scale is exactly 1.0 and the applied
    currents pass through bit-identically.
    """
    cap = grid_cap_kw(params, state) if cap_kw is None else cap_kw
    p_req = requested_power_kw(params, applied)
    scale = jnp.minimum(1.0, cap / jnp.maximum(p_req, 1e-9))
    return AllocationResult(
        applied=curtail(applied, scale),
        power_req_kw=p_req,
        power_kw=jnp.minimum(p_req, cap),
        cap_kw=cap,
        violation_kw=jnp.maximum(p_req - cap, 0.0),
    )


# ---------------------------------------------------------------------------
# Stage: deliver — charge stationed cars (constant rate over dt)
# ---------------------------------------------------------------------------
class ChargeResult(NamedTuple):
    state: EnvState
    e_car: jnp.ndarray  # (N,) kWh delivered into each car this step (signed)
    e_batt_net: jnp.ndarray  # () kWh grid-side battery energy (signed)
    e_repaid: jnp.ndarray  # (N,) kWh of this step's charge that repays
    #     earlier V2G discharge (settled at p_v2g_comp, not billed at p_sell)


def charge_bookkeeping(
    state: EnvState,
    applied: AppliedActions,
    e_car: jnp.ndarray,
    soc: jnp.ndarray,
    e_remain: jnp.ndarray,
    rhat: jnp.ndarray,
    e_batt: jnp.ndarray,
    batt_soc: jnp.ndarray,
) -> ChargeResult:
    """Deliver-stage state assembly from already-integrated pole physics.

    Shared by :func:`charge_cars` (staged lax path) and the fused-kernel hot
    path (``repro.kernels.chargax_step.ops``), which computes the pole
    integration in one slab pass and hands the results here — so the deadline
    tick, V2G debt settlement and energy counters exist exactly once.
    """
    # deadlines tick only on occupied ports; padded/idle lanes hold at 0
    # instead of drifting negative without bound
    t_remain = jnp.where(state.occupied > 0.5, state.t_remain - 1, state.t_remain)

    # V2G settlement bookkeeping: discharged energy becomes debt the station
    # owes the pack; subsequent charge repays debt first (settled at
    # p_v2g_comp in the reward, not billed at p_sell) so a discharge/recharge
    # cycle earns nothing beyond a genuine buy/sell price spread
    e_repaid = jnp.minimum(jnp.maximum(e_car, 0.0), state.v2g_debt)
    v2g_debt = state.v2g_debt - e_repaid + jnp.maximum(-e_car, 0.0)

    new_state = replace(
        state,
        evse_current=applied.evse_current,
        soc=soc,
        e_remain=e_remain,
        v2g_debt=v2g_debt,
        rhat=rhat,
        t_remain=t_remain,
        batt_current=applied.batt_current,
        batt_soc=batt_soc,
        energy_delivered=state.energy_delivered + jnp.sum(jnp.maximum(e_car, 0.0)),
        energy_discharged=state.energy_discharged
        + jnp.sum(jnp.maximum(-e_car, 0.0)),
    )
    return ChargeResult(new_state, e_car, e_batt, e_repaid)


def charge_cars(
    params: EnvParams, state: EnvState, applied: AppliedActions, dt_hours: float
) -> ChargeResult:
    e_car, soc, e_remain, rhat = pole_integrate(
        state.soc,
        state.e_remain,
        state.cap,
        state.rbar,
        state.tau,
        state.occupied,
        params.evse_voltage,
        applied.evse_current,
        1.0,
        dt_hours,
    )
    # battery pole: store eta*E charging, deliver E*eta grid-side discharging
    e_b, batt_soc, _, _ = pole_integrate(
        state.batt_soc,
        jnp.float32(BIG),
        params.batt_capacity,
        params.batt_max_current,
        params.batt_tau,
        1.0,
        params.batt_voltage,
        applied.batt_current,
        params.batt_eff,
        dt_hours,
    )
    return charge_bookkeeping(
        state, applied, e_car, soc, e_remain, rhat, e_b, batt_soc
    )


deliver = charge_cars


# ---------------------------------------------------------------------------
# Stage: depart_arrive
# ---------------------------------------------------------------------------
class DepartResult(NamedTuple):
    state: EnvState
    missing_kwh: jnp.ndarray  # () c_sat,0 numerator: unmet charge of u=0 leavers
    overtime_steps: jnp.ndarray  # () overtime of u=1 leavers (steps)
    early_steps: jnp.ndarray  # () early-finish steps of u=1 leavers


def depart_cars(state: EnvState) -> DepartResult:
    occ = state.occupied > 0.5
    leave_time = occ & (state.user_type < 0.5) & (state.t_remain <= 0)
    leave_charge = occ & (state.user_type >= 0.5) & (state.e_remain <= CHARGED_KWH)
    leaving = leave_time | leave_charge

    missing = jnp.sum(jnp.where(leave_time, jnp.maximum(state.e_remain, 0.0), 0.0))
    over = jnp.sum(
        jnp.where(leave_charge, jnp.maximum(-state.t_remain, 0).astype(jnp.float32), 0.0)
    )
    early = jnp.sum(
        jnp.where(leave_charge, jnp.maximum(state.t_remain, 0).astype(jnp.float32), 0.0)
    )

    keep = (~leaving).astype(jnp.float32)
    zi = jnp.zeros_like(state.soc)
    new_state = replace(
        state,
        evse_current=state.evse_current * keep,
        occupied=state.occupied * keep,
        soc=state.soc * keep,
        e_remain=state.e_remain * keep,
        v2g_debt=state.v2g_debt * keep,
        t_remain=state.t_remain * keep.astype(state.t_remain.dtype),
        rhat=state.rhat * keep,
        cap=state.cap * keep,
        rbar=state.rbar * keep,
        tau=jnp.where(leaving, zi, state.tau),
        user_type=state.user_type * keep,
        missing_kwh_cum=state.missing_kwh_cum + missing,
        overtime_steps_cum=state.overtime_steps_cum + over,
    )
    return DepartResult(new_state, missing, over, early)


class ArriveResult(NamedTuple):
    state: EnvState
    n_arrived: jnp.ndarray  # ()
    n_rejected: jnp.ndarray  # ()


def arrive_cars(
    params: EnvParams,
    state: EnvState,
    key: jax.Array,
    rate_extra: jnp.ndarray | None = None,
) -> ArriveResult:
    n = state.occupied.shape[0]
    spd = params.arrival_rate.shape[0]
    with annotate("env/arrive_count"):
        k_m, k_port = jax.random.split(key)

        n_days = params.arrival_day_scale.shape[0]
        rate = params.arrival_rate[jnp.mod(state.t, spd)] * params.arrival_day_scale[
            jnp.mod(state.day, n_days)
        ]
        if rate_extra is not None:
            # city coupling: the station's allocated share of the population-scale
            # arrival stream (repro.city) adds to its own walk-in table; a zero
            # share leaves the Poisson rate bit-identical to the uncoupled step
            rate = rate + rate_extra
        m = jax.random.poisson(k_m, rate).astype(jnp.int32)

        # padded fleet lanes (evse_mask == 0) never accept cars
        free = (state.occupied < 0.5) & (params.evse_mask > 0.5)
        n_free = jnp.sum(free.astype(jnp.int32))
        n_arrive = jnp.minimum(m, n_free)
        n_reject = jnp.maximum(m - n_free, 0)

        # first-come-first-served: fill free ports in index order
        rank = jnp.cumsum(free.astype(jnp.int32))  # 1-based among free ports
        assign = free & (rank <= n_arrive)
        a = assign.astype(jnp.float32)

    # --- per-port profile draws (one draw per port; only assigned ports
    # consume it).  Keys are folded per port index so the draw on port i is
    # independent of n — padding a station with extra lanes leaves the real
    # lanes' trajectories bit-for-bit unchanged (FleetEnv regression tests).
    with annotate("env/draw_cars"):
        with annotate("env/draw_model"):
            # fleet-mix drift: a (365, n_models) table selects the day's distribution
            probs = (
                params.car_probs
                if params.car_probs.ndim == 1
                else params.car_probs[jnp.mod(state.day, params.car_probs.shape[0])]
            )

        def draw_port(i):
            k_model, k_stay, k_soc0, k_tgt, k_u = jax.random.split(
                jax.random.fold_in(k_port, i), 5
            )
            with annotate("env/draw_model"):
                model = jax.random.choice(k_model, probs.shape[0], p=probs)
            z_stay = jax.random.normal(k_stay, ())
            with annotate("env/draw_soc0"):
                soc0 = jax.random.beta(k_soc0, params.soc0_a, params.soc0_b)
            z_tgt = jax.random.normal(k_tgt, ())
            bern = jax.random.bernoulli(k_u, params.p_time_sensitive)
            return model, z_stay, soc0, z_tgt, bern

        model, z_stay, soc0_raw, z_tgt, bern = jax.vmap(draw_port)(jnp.arange(n))

    # --- car profiles: the per-port model-table lookups -----------------------
    with annotate("env/car_lookup"):
        cap = params.car_capacity[model]
        tau = params.car_tau[model]
        car_kw = jnp.where(
            params.evse_is_dc > 0.5, params.car_dc_kw[model], params.car_ac_kw[model]
        )
        rbar = car_kw * 1000.0 / params.evse_voltage  # car-side current limit [A]

    # --- user profiles and the placement itself --------------------------------
    with annotate("env/place_cars"):
        stay_h = jnp.exp(params.stay_mu_log + params.stay_sigma * z_stay)
        steps_per_hour = spd / 24.0
        stay_steps = jnp.maximum((stay_h * steps_per_hour).astype(jnp.int32), 1)
        soc0 = jnp.clip(soc0_raw, 0.02, 0.95)
        target = jnp.clip(
            params.target_soc_mu + params.target_soc_std * z_tgt, soc0 + 0.05, 1.0
        )
        e_req = (target - soc0) * cap
        # u: 0 = time-sensitive (leaves at deadline), 1 = charge-sensitive
        u = 1.0 - bern.astype(jnp.float32)

        new_state = replace(
            state,
            occupied=state.occupied * (1 - a) + a,
            soc=state.soc * (1 - a) + a * soc0,
            e_remain=state.e_remain * (1 - a) + a * e_req,
            v2g_debt=state.v2g_debt * (1 - a),  # fresh arrivals carry no debt
            t_remain=jnp.where(assign, stay_steps, state.t_remain),
            rhat=state.rhat * (1 - a) + a * charge_rate(soc0, rbar, tau),
            cap=state.cap * (1 - a) + a * cap,
            rbar=state.rbar * (1 - a) + a * rbar,
            tau=jnp.where(assign, tau, state.tau),
            user_type=state.user_type * (1 - a) + a * u,
            cars_served=state.cars_served + n_arrive.astype(jnp.float32),
            cars_rejected=state.cars_rejected + n_reject.astype(jnp.float32),
        )
    return ArriveResult(new_state, n_arrive, n_reject)


class DepartArriveResult(NamedTuple):
    state: EnvState
    missing_kwh: jnp.ndarray  # ()
    overtime_steps: jnp.ndarray  # ()
    early_steps: jnp.ndarray  # ()
    n_arrived: jnp.ndarray  # ()
    n_rejected: jnp.ndarray  # ()


def depart_arrive(
    params: EnvParams,
    state: EnvState,
    key: jax.Array,
    rate_extra: jnp.ndarray | None = None,
) -> DepartArriveResult:
    """Departures then arrivals, splitting the step key for the Poisson draw.

    ``rate_extra`` (optional, scalar cars/step) feeds extra expected arrivals
    into the Poisson draw — the per-station input the city demand-allocation
    layer computes each step instead of a fixed table.
    """
    with annotate("env/departures"):
        departed = depart_cars(state)
    key, k_arr = jax.random.split(key)
    arrived = arrive_cars(params, departed.state, k_arr, rate_extra)
    return DepartArriveResult(
        arrived.state,
        departed.missing_kwh,
        departed.overtime_steps,
        departed.early_steps,
        arrived.n_arrived,
        arrived.n_rejected,
    )


# ---------------------------------------------------------------------------
# Stage: settle — energies, Eq. 1-3 reward, grid-axis penalties
# ---------------------------------------------------------------------------
class SettleResult(NamedTuple):
    reward: jnp.ndarray  # () Eq. 3 reward incl. grid penalties
    profit: jnp.ndarray  # () Eq. 2 profit
    energies: StepEnergies
    penalties: PenaltyTerms
    p_buy: jnp.ndarray  # () buy price this step
    setpoint_kw: jnp.ndarray  # () DSO setpoint in force
    setpoint_dev_kw: jnp.ndarray  # () |power_drawn - setpoint|


def settle(
    params: EnvParams,
    state: EnvState,  # the PRE-step state (this step's clock / price row)
    alloc: AllocationResult,
    charged: ChargeResult,
    moved: DepartArriveResult,
    dt_hours: float,
) -> SettleResult:
    """Reward settlement for one step.

    The base Eq. 1-3 algebra is untouched; the grid axis adds two linear
    penalty terms on top — ``grid_violation`` (kW the request overshot the
    feeder cap, before curtailment) and ``grid_setpoint`` (absolute tracking
    error against the DSO setpoint).  Both weights default to 0.0, making
    the additions exact bitwise no-ops for non-grid scenarios.
    """
    spd = state.price_buy.shape[0]
    e_pv = (
        params.pv_kw_table[
            jnp.mod(state.day, params.pv_kw_table.shape[0]),
            jnp.mod(state.t, spd),
        ]
        * dt_hours
    )
    energies = step_energies(
        params, charged.e_car, charged.e_batt_net, e_pv, charged.e_repaid
    )
    p_buy = state.price_buy[jnp.mod(state.t, spd)]
    reward, pi, pen = compute_reward(
        params,
        energies,
        p_buy,
        alloc.applied.constraint_excess,
        moved.missing_kwh,
        moved.overtime_steps,
        moved.early_steps,
        moved.n_rejected,
        charged.e_car,
        state.t,
        state.price_buy,
        dt_hours,
    )
    sp_table = params.grid_setpoint_kw_table
    setpoint = sp_table[
        jnp.mod(state.day, sp_table.shape[0]), jnp.mod(state.t, sp_table.shape[1])
    ]
    setpoint_dev = jnp.abs(alloc.power_kw - setpoint)
    w = params.weights
    reward = (
        reward - w.grid_violation * alloc.violation_kw - w.grid_setpoint * setpoint_dev
    )
    return SettleResult(reward, pi, energies, pen, p_buy, setpoint, setpoint_dev)


# ---------------------------------------------------------------------------
# Stage: advance_time — clock tick + midnight calendar rollover
# ---------------------------------------------------------------------------
def advance_time(params: EnvParams, state: EnvState, profit: jnp.ndarray) -> EnvState:
    """At midnight advance the day (mod table length) and reload the price
    row, so multi-day episodes see day-1+ prices, PV, arrival-day-scale and
    the weekday feature instead of replaying day 0 forever."""
    spd = state.price_buy.shape[0]
    t_next = state.t + 1
    n_days = params.price_buy_table.shape[0]
    midnight = jnp.mod(t_next, spd) == 0
    day_next = jnp.where(midnight, jnp.mod(state.day + 1, n_days), state.day)
    price_next = jnp.where(
        midnight, params.price_buy_table[day_next], state.price_buy
    )
    return replace(
        state,
        t=t_next,
        day=day_next,
        price_buy=price_next,
        profit_cum=state.profit_cum + profit,
    )


# ---------------------------------------------------------------------------
# Stage: observe
# ---------------------------------------------------------------------------
def observe(
    params: EnvParams,
    state: EnvState,
    *,
    steps_per_day: int,
    horizon_steps: int,
    near_steps: int,
    label_price_window: bool = False,
) -> jnp.ndarray:
    """Flat float32 observation (see ``ChargaxEnv.observation_space``).

    ``label_price_window`` names the price-horizon lookup ``env/price_window``.
    The step sets it; ``reset`` does not, so reset's lookup, which
    ``AutoReset`` runs on every step, stays under the wrapper's scope and
    outside the ``env/*`` stages.
    """
    spd = steps_per_day
    imax = params.evse_max_current
    port_feats = jnp.stack(
        [
            state.occupied,
            state.evse_current / imax,
            state.soc,
            state.e_remain / jnp.maximum(state.cap, 1.0),
            # V2G debt: how much of the remaining request is energy the
            # station borrowed (repaid at p_v2g_comp, not billed) — the
            # agent needs this to price discharge decisions correctly
            state.v2g_debt / jnp.maximum(state.cap, 1.0),
            jnp.clip(state.t_remain.astype(jnp.float32) / spd, -1.0, 1.0),
            state.rhat / imax,
            state.user_type,
        ],
        axis=-1,
    ).reshape(-1)
    batt_feats = jnp.stack(
        [state.batt_soc, state.batt_current / jnp.maximum(params.batt_max_current, 1.0)]
    )
    tf = state.t.astype(jnp.float32)
    phase = 2.0 * jnp.pi * tf / spd
    weekday = ((state.day % 7) < 5).astype(jnp.float32)
    time_feats = jnp.stack(
        [jnp.sin(phase), jnp.cos(phase), weekday, state.day.astype(jnp.float32) / 365.0]
    )
    with annotate("env/price_window") if label_price_window else contextlib.nullcontext():
        idx = jnp.mod(state.t, spd)
        ahead = state.price_buy[jnp.mod(idx + jnp.arange(horizon_steps), spd)]
        price_feats = jnp.stack(
            [state.price_buy[idx], jnp.mean(ahead[:near_steps]), jnp.mean(ahead)]
        )
    return jnp.concatenate([port_feats, batt_feats, time_feats, price_feats])
