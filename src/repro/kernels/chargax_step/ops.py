"""Jit'd wrapper for the fused Chargax station step.

Builds padded pole slabs from core env structures, dispatches to the Pallas
kernel (TPU) or the jnp reference (CPU / other backends), and unpacks
results back into env-shaped pieces.  The battery is pole index ``n_evse``
(the paper's (N+1)-th pole).

Two granularities are exposed:

- :func:`fused_step` — pole-slab in, pole-slab out; the kernel-parity
  surface (``tests/kernels``).
- :func:`fused_transition` — EnvState in, ``(AllocationResult,
  ChargeResult)`` out; the hot-path entry :meth:`ChargaxEnv.step` routes
  through when ``EnvConfig.fused_step`` is on.  On CPU it runs
  :func:`fused_request` (bit-identical to the staged ``apply_actions`` —
  natural-shape clips, padded-matmul Eq. 5) plus the staged
  allocate/deliver stages; on TPU it runs the Pallas slab kernel and
  reuses :func:`repro.core.transition.charge_bookkeeping` for the state
  assembly.

Backend dispatch (:func:`resolve_impl`) honours the ``CHARGAX_FUSED_IMPL``
environment variable (``pallas`` | ``interpret`` | ``ref``) so CI can force
Pallas interpret mode on CPU.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import EnvParams, EnvState
from repro.core.transition import (
    AllocationResult,
    AppliedActions,
    ChargeResult,
    allocate,
    charge_bookkeeping,
    charge_cars,
    constraint_scale,
    grid_cap_kw,
    pole_bounds,
    pole_clip,
)
from repro.kernels.chargax_step import ref
from repro.kernels.chargax_step.kernel import chargax_fused_step
from repro.kernels.chargax_step.ref import BIG, FusedOut, PoleParams, PoleSlabs

IMPL_ENV_VAR = "CHARGAX_FUSED_IMPL"


def resolve_impl(impl: str = "auto") -> str:
    """Resolve the fused-step backend: pallas | interpret | ref.

    ``auto`` picks the Pallas kernel on TPU (the slab kernel is written to
    Mosaic's 8x128 tiling) and the jnp reference elsewhere (on CPU the
    reference is also the bit-exact choice — see :func:`fused_request`).  The ``CHARGAX_FUSED_IMPL`` env var overrides
    ``auto`` (CI uses it to exercise Pallas interpret mode on CPU).
    """
    if impl != "auto":
        return impl
    forced = os.environ.get(IMPL_ENV_VAR, "").strip().lower()
    if forced in ("pallas", "interpret", "ref"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pad_lanes(x: np.ndarray | jnp.ndarray, target: int, fill=0.0):
    pad = target - x.shape[-1]
    if pad <= 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths, constant_values=fill)


def build_pole_params(params: EnvParams, n_pad: int | None = None) -> PoleParams:
    """Lift EnvParams into lane-padded PoleParams (poles = EVSEs + battery).

    When ``EnvConfig.fused_step`` hoisted the pack at ``make_params`` time it
    lives on ``params.pole`` and is returned as-is — per-step callers never
    rebuild it.
    """
    if params.pole is not None and n_pad is None:
        return params.pole
    n = params.evse_voltage.shape[0]
    p = n_pad or ((n + 1 + 127) // 128 * 128)

    voltage = _pad_lanes(jnp.append(params.evse_voltage, params.batt_voltage), p, 1.0)
    imax = _pad_lanes(jnp.append(params.evse_max_current, params.batt_max_current), p)
    ones = jnp.ones((n,), jnp.float32)
    eff = _pad_lanes(jnp.append(ones, params.batt_eff), p, 1.0)
    # grid-side watts per charging amp (requested_power_kw's per-lane factor)
    power_w = _pad_lanes(
        jnp.append(
            params.evse_voltage / jnp.maximum(params.evse_path_eff, 1e-9),
            jnp.asarray(params.batt_voltage, jnp.float32),
        ),
        p,
    )

    nn_real, n_leaf = params.member.shape  # member already has the battery col
    nn = (nn_real + 7) // 8 * 8
    member = jnp.zeros((nn, p), jnp.float32).at[:nn_real, : n + 1].set(params.member)
    budget = jnp.full((nn,), BIG, jnp.float32).at[:nn_real].set(params.node_budget)
    return PoleParams(voltage, imax, eff, member, budget, power_w)


def build_slabs(
    params: EnvParams,
    state: EnvState,
    target_evse: jnp.ndarray,
    target_batt: jnp.ndarray,
    pp: PoleParams,
) -> PoleSlabs:
    """Build (..., P) pole slabs from env state (leading dims = env batch)."""
    p = pp.voltage.shape[-1]

    def cat(evse_val, batt_scalar, fill=0.0):
        batt = jnp.broadcast_to(batt_scalar, target_batt.shape)
        x = jnp.concatenate([evse_val, batt[..., None]], axis=-1)
        return _pad_lanes(x, p, fill)

    return PoleSlabs(
        target=cat(target_evse, target_batt * 1.0),
        occupied=cat(state.occupied, 1.0),
        soc=cat(state.soc, state.batt_soc),
        e_remain=cat(state.e_remain, BIG),
        cap=cat(state.cap, params.batt_capacity),
        rbar=cat(state.rbar, params.batt_max_current),
        tau=cat(state.tau, params.batt_tau),
    )


def fused_step(
    params: EnvParams,
    state: EnvState,
    target_evse: jnp.ndarray,  # (..., N)
    target_batt: jnp.ndarray,  # (...,)
    dt_hours: float,
    *,
    cap_kw: jnp.ndarray | None = None,  # (...,) feeder cap [kW]; None = unlimited
    impl: str = "auto",  # auto | pallas | interpret | ref
    block_envs: int = 256,
) -> FusedOut:
    """Stages 1-3 of the transition for a (possibly batched) env state.

    Returns pole-indexed FusedOut; callers slice [..., :N] for EVSEs and
    [..., N] for the battery.
    """
    impl = resolve_impl(impl)
    pp = build_pole_params(params)
    slabs = build_slabs(params, state, target_evse, target_batt, pp)

    if impl == "ref":
        return ref.fused_step_ref(slabs, pp, dt_hours, cap_kw)

    # pallas path: flatten env batch, pad to block multiple.  The block
    # adapts downward so a per-env call under vmap (b == 1) pads to the
    # 8-sublane minimum tile, not to 256 envs.
    lead = slabs.soc.shape[:-1]
    p = slabs.soc.shape[-1]
    b = int(np.prod(lead)) if lead else 1
    block = min(block_envs, (b + 7) // 8 * 8)
    bp = (b + block - 1) // block * block

    def flat(x):
        x = x.reshape(b, p)
        return jnp.pad(x, ((0, bp - b), (0, 0)))

    slab_arrays = tuple(flat(x) for x in slabs)

    cap = jnp.full(lead, BIG, jnp.float32) if cap_kw is None else cap_kw
    cap = jnp.broadcast_to(jnp.asarray(cap, jnp.float32), lead).reshape(b, 1)
    cap = jnp.pad(cap, ((0, bp - b), (0, 0)), constant_values=BIG)
    cap = jnp.broadcast_to(cap, (bp, 128))

    def sub(x):  # params rows padded to 8 sublanes
        return jnp.broadcast_to(x, (8,) + x.shape)

    param_arrays = (
        sub(pp.voltage), sub(pp.imax), sub(pp.eff), sub(pp.power_w),
        pp.member, jnp.broadcast_to(pp.node_budget[:, None], (pp.member.shape[0], 128)),
    )
    outs = chargax_fused_step(
        slab_arrays,
        param_arrays,
        cap,
        dt_hours=dt_hours,
        block_envs=block,
        interpret=(impl == "interpret"),
    )
    current, soc, e_remain, rhat, e_pole, excess, p_req = outs
    shape = lead + (p,)
    return FusedOut(
        current=current[:b].reshape(shape),
        soc=soc[:b].reshape(shape),
        e_remain=e_remain[:b].reshape(shape),
        rhat=rhat[:b].reshape(shape),
        e_pole=e_pole[:b].reshape(shape),
        excess=excess[:b, 0].reshape(lead),
        p_req=p_req[:b, 0].reshape(lead),
    )


def fused_request(
    params: EnvParams,
    state: EnvState,
    target_evse: jnp.ndarray,
    target_batt: jnp.ndarray,
    dt_hours: float,
) -> AppliedActions:
    """Bit-exact fused form of the staged ``apply_actions`` request stage.

    Bounds/clip/battery/Eq. 5 all run the staged pipeline's own helpers at
    their natural shapes, so XLA lowers the fused route identically to the
    staged one — parity is structural, not a tolerance.  (The padded
    Eq. 5 reduction lives only in the slab kernel path, where its reduction
    order is covered by fp32 tolerance, not bitwise equality: XLA's
    natural-shape matvec and a 128-lane reduction associate the sum
    differently for some inputs.)
    """
    up, down = pole_bounds(
        state.soc, state.e_remain, state.cap, state.rbar, state.tau,
        params.evse_voltage, params.evse_max_current, 1.0, dt_hours,
    )
    i_evse = pole_clip(target_evse, up, down, state.occupied)
    b_up, b_down = pole_bounds(
        state.batt_soc, jnp.float32(BIG), params.batt_capacity,
        params.batt_max_current, params.batt_tau,
        params.batt_voltage, params.batt_max_current,
        params.batt_eff, dt_hours,
    )
    i_batt = pole_clip(target_batt, b_up, b_down, 1.0)

    leaf = jnp.concatenate([i_evse, i_batt[None]])
    scale, excess = constraint_scale(leaf, params.member, params.node_budget)
    leaf = leaf * scale
    return AppliedActions(leaf[:-1], leaf[-1], excess)


def fused_transition(
    params: EnvParams,
    state: EnvState,
    target_evse: jnp.ndarray,
    target_batt: jnp.ndarray,
    dt_hours: float,
    *,
    cap_kw: jnp.ndarray | None = None,
    impl: str = "auto",
    block_envs: int = 256,
) -> tuple[AllocationResult, ChargeResult]:
    """request + allocate + deliver for ONE env state (the step hot path).

    Drop-in replacement for the staged ``apply_actions`` →
    ``transition.allocate`` → ``charge_cars`` sequence.  ``ref`` (CPU
    default) is bit-identical to the staged pipeline; ``pallas`` /
    ``interpret`` run the slab kernel and agree within fp32 op-reorder
    tolerance.
    """
    impl = resolve_impl(impl)
    cap = grid_cap_kw(params, state) if cap_kw is None else cap_kw

    if impl == "ref":
        applied = fused_request(params, state, target_evse, target_batt, dt_hours)
        alloc = allocate(params, state, applied, cap)
        return alloc, charge_cars(params, state, alloc.applied, dt_hours)

    out = fused_step(
        params, state, target_evse, target_batt, dt_hours,
        cap_kw=cap, impl=impl, block_envs=block_envs,
    )
    n = params.evse_voltage.shape[0]
    applied = AppliedActions(out.current[..., :n], out.current[..., n], out.excess)
    alloc = AllocationResult(
        applied=applied,
        power_req_kw=out.p_req,
        power_kw=jnp.minimum(out.p_req, cap),
        cap_kw=cap,
        violation_kw=jnp.maximum(out.p_req - cap, 0.0),
    )
    charged = charge_bookkeeping(
        state,
        applied,
        out.e_pole[..., :n],
        out.soc[..., :n],
        out.e_remain[..., :n],
        out.rhat[..., :n],
        out.e_pole[..., n],
        out.soc[..., n],
    )
    return alloc, charged
