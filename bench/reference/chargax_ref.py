"""Plain reference of the Chargax MDP (arXiv:2507.01522, App. A) for one station.

Written from the paper's equations and the configuration file alone: it
imports nothing of ``repro``.  One environment is a dict of arrays; batches
come from ``jax.vmap`` in the callers.  The tables it reads (station tree,
prices, arrival rates, car mix, PV) are the configuration's data, handed in
as a dict of arrays whose fingerprint ``bench/harness/tables.py`` checks
against the one recorded beside the configuration.

Random draws follow the environment's published key discipline, so one key
gives the same cars and the same episode as the system under test:

* reset: ``k_day, _ = split(key)``; the episode's day is ``randint(k_day, 0, 365)``;
* step: ``_, k_arr = split(key)``; ``k_m, k_port = split(k_arr)``; the arrivals
  are ``poisson(k_m, rate)``; port ``i`` draws (model, stay, soc0, target,
  time-sensitive) from ``split(fold_in(k_port, i), 5)``;
* a batch of N envs splits its key into N, env ``i`` takes the ``i``-th;
  auto-reset splits the step key into (step, reset) first.

``ftype`` is the float type of the state and of the arithmetic: float32 is
the configuration's; bfloat16 makes the lower-precision control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BIG = 1e30  # energy request of the battery pole: never binds
CHARGED_KWH = 1e-6  # a charge-sensitive car leaves once at most this much is left

STATE_FLOATS = (
    "cur", "occ", "soc", "e_rem", "debt", "b_cur", "b_soc", "rhat", "cap",
    "rbar", "tau", "utype", "price", "profit_cum", "delivered", "discharged",
    "served", "rejected", "missing_cum", "overtime_cum",
)


def cast_tables(tables: dict, ftype) -> dict:
    """Float tables in ``ftype`` (integers and the car-mix probabilities,
    which only feed the random draws, stay as they are)."""
    keep = {"car_probs"}
    return {
        k: (v.astype(ftype) if jnp.issubdtype(v.dtype, jnp.floating) and k not in keep else v)
        for k, v in tables.items()
    }


def _rate_curve(soc, rbar, tau):
    """Car charge curve: flat at ``rbar`` up to the knee ``tau``, then linear to 0."""
    return jnp.where(soc <= tau, rbar, rbar * (1.0 - soc) / jnp.maximum(1.0 - tau, 1e-6))


def _amps_for(energy_kwh, volts, dt):
    return energy_kwh * 1000.0 / jnp.maximum(volts * dt, 1e-9)


def _bounds(soc, e_rem, cap, rbar, tau, volts, imax, eff, dt):
    """Charge limit (>= 0) and discharge limit (<= 0) of one pole [A]."""
    up = jnp.minimum(
        jnp.minimum(_rate_curve(soc, rbar, tau), imax),
        jnp.minimum(
            _amps_for(e_rem, volts, dt),
            (1.0 - soc) * cap * 1000.0 / jnp.maximum(volts * dt * eff, 1e-9),
        ),
    )
    down = -jnp.minimum(
        jnp.minimum(_rate_curve(1.0 - soc, rbar, tau), imax),
        soc * cap * eff * 1000.0 / jnp.maximum(volts * dt, 1e-9),
    )
    return up, down


def _integrate(soc, e_rem, cap, rbar, tau, occ, volts, amps, eff, dt):
    """Energy of one pole over the step, and its new soc, request and rate."""
    e = volts * amps * dt / 1000.0
    stored = jnp.where(e >= 0, e * eff, e / eff)
    soc_new = jnp.clip(soc + stored / jnp.maximum(cap, 1e-6), 0.0, 1.0)
    room = jnp.where(e_rem >= 0.5 * BIG, BIG, (1.0 - soc_new) * cap)
    whole = amps >= _amps_for(e_rem, volts, dt)
    e_rem_new = jnp.where(whole, 0.0, jnp.minimum(jnp.maximum(e_rem - e, 0.0), room))
    return e, soc_new, e_rem_new, _rate_curve(soc_new, rbar, tau) * occ


def reset(key, tab: dict, ftype=jnp.float32) -> dict:
    n = tab["evse_voltage"].shape[0]
    k_day, _ = jax.random.split(key)
    day = jax.random.randint(k_day, (), 0, tab["price_buy_table"].shape[0])
    s = {k: jnp.zeros((n,), ftype) for k in ("cur", "occ", "soc", "e_rem", "debt", "rhat", "cap", "rbar", "tau", "utype")}
    for k in ("b_cur", "profit_cum", "delivered", "discharged", "served", "rejected", "missing_cum", "overtime_cum"):
        s[k] = jnp.zeros((), ftype)
    s["b_soc"] = tab["batt_init_soc"].astype(ftype)
    s["t_rem"] = jnp.zeros((n,), jnp.int32)
    s["t"] = jnp.int32(0)
    s["day"] = day
    s["price"] = tab["price_buy_table"][day].astype(ftype)
    return s


def observe(s: dict, tab: dict, spd: int, horizon: int, near: int):
    imax = tab["evse_max_current"]
    ports = jnp.stack(
        [
            s["occ"], s["cur"] / imax, s["soc"], s["e_rem"] / jnp.maximum(s["cap"], 1.0),
            s["debt"] / jnp.maximum(s["cap"], 1.0),
            jnp.clip(s["t_rem"].astype(s["soc"].dtype) / spd, -1.0, 1.0),
            s["rhat"] / imax, s["utype"],
        ],
        axis=-1,
    ).reshape(-1)
    batt = jnp.stack([s["b_soc"], s["b_cur"] / jnp.maximum(tab["batt_max_current"], 1.0)])
    t = s["t"].astype(s["soc"].dtype)
    ang = 2.0 * math.pi * t / spd
    clock = jnp.stack(
        [jnp.sin(ang), jnp.cos(ang), ((s["day"] % 7) < 5).astype(t.dtype), s["day"].astype(t.dtype) / 365.0]
    )
    i = jnp.mod(s["t"], spd)
    ahead = s["price"][jnp.mod(i + jnp.arange(horizon), spd)]
    price = jnp.stack([s["price"][i], jnp.mean(ahead[:near]), jnp.mean(ahead)])
    return jnp.concatenate([ports, batt.astype(ports.dtype), clock.astype(ports.dtype), price.astype(ports.dtype)])


def step(key, s: dict, action, tab: dict, env_cfg: dict, ftype=jnp.float32):
    """One transition of one station; returns (state, reward, done, info)."""
    dt = env_cfg["dt_minutes"] / 60.0
    spd = int(round(24 * 60 / env_cfg["dt_minutes"]))
    n_ep = int(round(env_cfg.get("episode_hours", 24.0) * 60.0 / env_cfg["dt_minutes"]))
    disc = float(env_cfg["discretization"])
    volts, imax = tab["evse_voltage"], tab["evse_max_current"]
    path_eff = tab["evse_path_eff"]
    bv, bimax, bcap = tab["batt_voltage"], tab["batt_max_current"], tab["batt_capacity"]
    beff, btau = tab["batt_eff"], tab["batt_tau"]
    t, day = s["t"], s["day"]
    n_days = tab["price_buy_table"].shape[0]

    # action levels 0..2D -> target amps; without V2G ports never discharge
    frac = (action.astype(ftype) - disc) / disc
    port_frac = frac[:-1] if env_cfg.get("allow_v2g") else jnp.maximum(frac[:-1], 0.0)
    tgt, b_tgt = port_frac * imax, frac[-1] * bimax

    up, down = _bounds(s["soc"], s["e_rem"], s["cap"], s["rbar"], s["tau"], volts, imax, 1.0, dt)
    amps = jnp.clip(tgt, down, jnp.maximum(up, 0.0)) * s["occ"]
    b_up, b_down = _bounds(s["b_soc"], jnp.asarray(BIG, ftype), bcap, bimax, btau, bv, bimax, beff, dt)
    b_amps = jnp.clip(b_tgt, b_down, jnp.maximum(b_up, 0.0))

    # Eq. 5: every node of the tree carries at most its budget (sum of |I|
    # of the leaves under it); each leaf is scaled by its tightest ancestor
    leaves = jnp.concatenate([amps, b_amps[None]])
    member = tab["member"]
    load = jnp.sum(member * jnp.abs(leaves)[None, :], axis=1)
    node_s = jnp.minimum(1.0, tab["node_budget"] / jnp.maximum(load, 1e-9))
    excess = jnp.max(jnp.maximum(load - tab["node_budget"], 0.0))
    leaf_s = jnp.min(jnp.where(member > 0, node_s[:, None], jnp.inf), axis=0)
    leaves = leaves * jnp.where(jnp.isfinite(leaf_s), leaf_s, 1.0)
    amps, b_amps = leaves[:-1], leaves[-1]

    # feeder envelope: charging power above the cap is shed proportionally
    grid_cap = tab["grid_cap_kw_table"][jnp.mod(day, n_days), jnp.mod(t, spd)]
    p_req = (jnp.sum(volts * jnp.maximum(amps, 0.0) / path_eff) + bv * jnp.maximum(b_amps, 0.0)) / 1000.0
    shed = jnp.minimum(1.0, grid_cap / jnp.maximum(p_req, 1e-9))
    amps = jnp.where(amps > 0.0, amps * shed, amps)
    b_amps = jnp.where(b_amps > 0.0, b_amps * shed, b_amps)
    p_drawn = jnp.minimum(p_req, grid_cap)
    violation = jnp.maximum(p_req - grid_cap, 0.0)

    # energy into cars and battery over the step
    e_car, soc, e_rem, rhat = _integrate(
        s["soc"], s["e_rem"], s["cap"], s["rbar"], s["tau"], s["occ"], volts, amps, 1.0, dt
    )
    e_b, b_soc, _, _ = _integrate(
        s["b_soc"], jnp.asarray(BIG, ftype), bcap, bimax, btau, 1.0, bv, b_amps, beff, dt
    )
    occ = s["occ"]
    t_rem = jnp.where(occ > 0.5, s["t_rem"] - 1, s["t_rem"])
    repaid = jnp.minimum(jnp.maximum(e_car, 0.0), s["debt"])
    debt = s["debt"] - repaid + jnp.maximum(-e_car, 0.0)
    e_in, e_out = jnp.sum(jnp.maximum(e_car, 0.0)), jnp.sum(jnp.maximum(-e_car, 0.0))

    # departures: time-sensitive at the deadline, charge-sensitive when done
    here = occ > 0.5
    gone_t = here & (s["utype"] < 0.5) & (t_rem <= 0)
    gone_c = here & (s["utype"] >= 0.5) & (e_rem <= CHARGED_KWH)
    gone = gone_t | gone_c
    missing = jnp.sum(jnp.where(gone_t, jnp.maximum(e_rem, 0.0), 0.0))
    over = jnp.sum(jnp.where(gone_c, jnp.maximum(-t_rem, 0).astype(ftype), 0.0))
    early = jnp.sum(jnp.where(gone_c, jnp.maximum(t_rem, 0).astype(ftype), 0.0))
    stay = (~gone).astype(ftype)
    cur, occ, soc, e_rem, debt = amps * stay, occ * stay, soc * stay, e_rem * stay, debt * stay
    t_rem = t_rem * stay.astype(jnp.int32)
    rhat, cap, rbar = rhat * stay, s["cap"] * stay, s["rbar"] * stay
    tau = jnp.where(gone, 0.0, s["tau"]).astype(ftype)
    utype = s["utype"] * stay

    # arrivals: Poisson count, first come first served on free ports
    _, k_arr = jax.random.split(key)
    k_m, k_port = jax.random.split(k_arr)
    rate = tab["arrival_rate"][jnp.mod(t, spd)] * tab["arrival_day_scale"][
        jnp.mod(day, tab["arrival_day_scale"].shape[0])
    ]
    m = jax.random.poisson(k_m, rate.astype(jnp.float32)).astype(jnp.int32)
    free = (occ < 0.5) & (tab["evse_mask"] > 0.5)
    n_free = jnp.sum(free.astype(jnp.int32))
    n_in = jnp.minimum(m, n_free)
    n_rej = jnp.maximum(m - n_free, 0)
    take = free & (jnp.cumsum(free.astype(jnp.int32)) <= n_in)
    a = take.astype(ftype)
    probs = tab["car_probs"]
    if probs.ndim == 2:
        probs = probs[jnp.mod(day, probs.shape[0])]

    def draws(i):
        k = jax.random.split(jax.random.fold_in(k_port, i), 5)
        return (
            jax.random.choice(k[0], probs.shape[0], p=probs),
            jax.random.normal(k[1], ()),
            jax.random.beta(k[2], tab["soc0_a"].astype(jnp.float32), tab["soc0_b"].astype(jnp.float32)),
            jax.random.normal(k[3], ()),
            jax.random.bernoulli(k[4], tab["p_time_sensitive"].astype(jnp.float32)),
        )

    model, z_stay, soc0, z_tgt, sensitive = jax.vmap(draws)(jnp.arange(occ.shape[0]))
    z_stay, soc0, z_tgt = z_stay.astype(ftype), soc0.astype(ftype), z_tgt.astype(ftype)
    c_cap = tab["car_capacity"][model]
    c_tau = tab["car_tau"][model]
    c_kw = jnp.where(tab["evse_is_dc"] > 0.5, tab["car_dc_kw"][model], tab["car_ac_kw"][model])
    c_rbar = c_kw * 1000.0 / volts
    stay_h = jnp.exp(tab["stay_mu_log"] + tab["stay_sigma"] * z_stay)
    stay_steps = jnp.maximum((stay_h * (spd / 24.0)).astype(jnp.int32), 1)
    soc0 = jnp.clip(soc0, 0.02, 0.95)
    target = jnp.clip(tab["target_soc_mu"] + tab["target_soc_std"] * z_tgt, soc0 + 0.05, 1.0)
    occ = occ * (1 - a) + a
    soc = soc * (1 - a) + a * soc0
    e_rem = e_rem * (1 - a) + a * ((target - soc0) * c_cap)
    debt = debt * (1 - a)
    t_rem = jnp.where(take, stay_steps, t_rem)
    rhat = rhat * (1 - a) + a * _rate_curve(soc0, c_rbar, c_tau)
    cap = cap * (1 - a) + a * c_cap
    rbar = rbar * (1 - a) + a * c_rbar
    tau = jnp.where(take, c_tau, tau)
    utype = utype * (1 - a) + a * (1.0 - sensitive.astype(ftype))

    # Eq. 1-3: energy balance, profit, penalties (pre-step clock and prices)
    ti = jnp.mod(t, spd)
    e_pv = tab["pv_kw_table"][jnp.mod(day, tab["pv_kw_table"].shape[0]), ti] * dt
    e_grid = (
        jnp.sum(jnp.where(e_car > 0, e_car / path_eff, 0.0))
        + jnp.sum(jnp.where(e_car < 0, e_car * path_eff, 0.0))
        + e_b
        - e_pv
    )
    p_buy = s["price"][ti]
    grid_cost = jnp.where(e_grid > 0, p_buy * e_grid, tab["grid_sell_discount"] * p_buy * e_grid)
    demand = tab["demand_charge_rate"] * jnp.maximum(jnp.maximum(e_grid, 0.0) / dt - tab["demand_contract_kw"], 0.0)
    repaid_sum = jnp.sum(repaid)
    revenue = tab["p_sell"] * (e_in - repaid_sum) + tab["p_v2g_comp"] * repaid_sum - tab["p_v2g_comp"] * e_out
    profit = revenue - grid_cost - demand - tab["facility_cost"] * dt
    moer = tab["moer_scale"] * jnp.clip(p_buy / jnp.maximum(jnp.mean(s["price"]), 1e-6), 0.2, 3.0)
    d_grid = tab["grid_demand_amp"] * (0.6 + 0.4 * jnp.sin(2.0 * math.pi * (t.astype(ftype) / spd) - 0.5 * math.pi))
    w = {k[2:]: v for k, v in tab.items() if k.startswith("w_")}
    setpoint = tab["grid_setpoint_kw_table"][jnp.mod(day, n_days), ti]
    reward = (
        profit
        - w["constraint"] * excess
        - w["satisfaction_time"] * missing
        - w["satisfaction_charge"] * (over - w["early_finish_beta"] * early)
        - w["sustainability"] * moer * jnp.maximum(e_grid, 0.0)
        - w["rejected"] * n_rej.astype(ftype)
        - w["degradation"] * (jnp.abs(jnp.minimum(e_b, 0.0)) + jnp.sum(jnp.abs(jnp.minimum(e_car, 0.0))))
        - w["grid_stability"] * jnp.abs(jnp.sum(e_car) - d_grid)
        - w["grid_violation"] * violation
        - w["grid_setpoint"] * jnp.abs(p_drawn - setpoint)
    )

    # clock: at midnight the next day's price row comes in
    t_next = t + 1
    midnight = jnp.mod(t_next, spd) == 0
    day_next = jnp.where(midnight, jnp.mod(day + 1, n_days), day)
    price = jnp.where(midnight, tab["price_buy_table"][day_next].astype(ftype), s["price"])
    new = dict(
        cur=cur, occ=occ, soc=soc, e_rem=e_rem, debt=debt, b_cur=b_amps, b_soc=b_soc,
        t_rem=t_rem, rhat=rhat, cap=cap, rbar=rbar, tau=tau, utype=utype,
        t=t_next, day=day_next, price=price,
        profit_cum=s["profit_cum"] + profit,
        delivered=s["delivered"] + e_in,
        discharged=s["discharged"] + e_out,
        served=s["served"] + n_in.astype(ftype),
        rejected=s["rejected"] + n_rej.astype(ftype),
        missing_cum=s["missing_cum"] + missing,
        overtime_cum=s["overtime_cum"] + over,
    )
    new = {k: (v.astype(ftype) if k in STATE_FLOATS else v) for k, v in new.items()}
    info = {
        "profit": profit.astype(ftype),
        "energy_delivered": e_in.astype(ftype),
        "arrived": n_in.astype(ftype),
        "rejected": n_rej.astype(ftype),
        "missing_kwh": missing.astype(ftype),
    }
    return new, reward.astype(ftype), t_next >= n_ep, info


def batch_reset(key, tabs: dict, n_envs: int, ftype=jnp.float32, rows=None):
    """Reset envs split evenly over the stacked scenarios of ``tabs``
    (leading axis S): env ``i`` of ``n_envs`` takes the ``i``-th key and
    scenario ``i // (n_envs // S)``.  ``rows`` (``(S, E)`` env indices)
    picks which envs; default all.  State leaves come back ``(S, E, ...)``."""
    n_scen = jax.tree_util.tree_leaves(tabs)[0].shape[0]
    if rows is None:
        rows = jnp.arange(n_envs).reshape(n_scen, n_envs // n_scen)
    keys = jax.random.split(key, n_envs)[rows]
    return jax.vmap(jax.vmap(lambda k, tb: reset(k, tb, ftype), in_axes=(0, None)))(keys, tabs)


def batch_autoreset_step(key, states, actions, tabs, env_cfg, n_envs: int, ftype=jnp.float32, rows=None):
    """Step a nested ``(S, E)`` batch with auto-reset: where an episode ends
    the state restarts from a fresh reset, while reward and info still
    describe the finishing step.  ``rows`` (``(S, E)`` flat env indices)
    selects which of the ``n_envs`` per-env keys each batch entry takes;
    default: all envs in order."""
    n_scen = jax.tree_util.tree_leaves(tabs)[0].shape[0]
    k_step, k_reset = jax.random.split(key)
    ks, kr = jax.random.split(k_step, n_envs), jax.random.split(k_reset, n_envs)
    if rows is None:
        rows = jnp.arange(n_envs).reshape(n_scen, n_envs // n_scen)
    ks, kr = ks[rows], kr[rows]

    def one(k_s, k_r, s, a, tb):
        ns, r, done, info = step(k_s, s, a, tb, env_cfg, ftype)
        fresh = reset(k_r, tb, ftype)
        ns = jax.tree_util.tree_map(lambda f, x: jnp.where(done, f, x), fresh, ns)
        return ns, r, done, info

    inner = jax.vmap(one, in_axes=(0, 0, 0, 0, None))
    return jax.vmap(inner, in_axes=(0, 0, 0, 0, 0))(ks, kr, states, actions, tabs)


def batch_observe(states, tabs, env_cfg):
    spd = int(round(24 * 60 / env_cfg["dt_minutes"]))
    horizon = max(int(env_cfg.get("obs_price_horizon_hours", 4.0) * spd / 24), 1)
    near = max(int(spd / 24), 1)
    f = lambda s, tb: observe(s, tb, spd, horizon, near)  # noqa: E731
    return jax.vmap(jax.vmap(f, in_axes=(0, None)), in_axes=(0, 0))(states, tabs)
