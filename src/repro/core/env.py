"""Chargax environment — the canonical ``repro.envs.Environment`` implementation.

    env = ChargaxEnv(EnvConfig(scenario="shopping"))
    obs, state = env.reset(key)
    ts = env.step(key, state, action)            # ts: repro.envs.TimeStep
    obs, state, reward, done, info = ts          # ...which unpacks as before

``reset``/``step`` are pure and jit/vmap/scan-compatible; all configuration
that changes array *shapes* or python control flow lives in the static
``EnvConfig``, everything numeric lives in the ``EnvParams`` pytree so sweeps
(alpha weights, price years, traffic levels) never recompile.  Shapes and
bounds are typed: ``env.observation_space`` / ``env.action_space``
(:mod:`repro.envs.spaces`); batching, auto-reset and fleet composition come
from the wrapper stack in :mod:`repro.envs.wrappers`.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import datasets, station, transition
from repro.core.state import EnvParams, EnvState, RewardWeights
from repro.core.transition import GRID_CAP_UNLIMITED, AllocationResult
from repro.envs import spaces
from repro.envs.base import Environment, TimeStep
from repro.obs import annotate
from repro.utils import steps_per_day


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    """Static environment configuration (hashable; part of the jit cache key)."""

    # scenario selection (paper Table 1)
    scenario: str = "shopping"  # user profile: highway|residential|work|shopping
    traffic: str = "medium"  # low|medium|high
    price_region: str = "NL"  # NL|FR|DE
    price_year: int = 2021
    car_region: str = "EU"  # EU|US|World
    architecture: str = "paper_16"  # key into station.ARCHITECTURES
    # timing
    dt_minutes: float = 5.0
    episode_hours: float = 24.0
    # action space
    discretization: int = 10  # paper Table 3
    allow_v2g: bool = False  # car discharging
    action_mode: str = "direct"  # "direct" | "delta"
    # battery
    battery: bool = True
    # observation
    obs_price_horizon_hours: float = 4.0
    # fleet padding: pad the station to this many EVSEs/nodes (0 = no padding)
    # so heterogeneous stations share one array shape and one jit cache entry
    pad_evse: int = 0
    pad_nodes: int = 0
    # hot path: route request/allocate/deliver through the fused step kernel
    # (kernels/chargax_step) — Pallas on TPU, bit-exact jnp ref elsewhere;
    # see docs/kernels.md.  Off by default: flag-off params and HLO are
    # identical to builds that predate the flag.
    fused_step: bool = False

    @property
    def steps_per_day(self) -> int:
        return steps_per_day(self.dt_minutes)

    @property
    def episode_steps(self) -> int:
        return int(round(self.episode_hours * 60.0 / self.dt_minutes))

    @property
    def dt_hours(self) -> float:
        return self.dt_minutes / 60.0


class ChargaxEnv(Environment):
    """Paper's environment. Instances are cheap; arrays live in ``default_params``."""

    def __init__(self, config: EnvConfig | None = None):
        self.config = config or EnvConfig()
        layout = station.ARCHITECTURES[self.config.architecture]()
        # the env config is authoritative about battery presence
        if layout.battery.enabled != self.config.battery:
            layout = dataclasses.replace(
                layout,
                battery=dataclasses.replace(
                    layout.battery, enabled=self.config.battery
                ),
            )
        if self.config.pad_evse or self.config.pad_nodes:
            layout = station.pad_layout(
                layout,
                max(self.config.pad_evse, layout.n_evse),
                max(self.config.pad_nodes, layout.n_nodes),
            )
        self.layout = layout
        self.n_evse = layout.n_evse

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    @cached_property
    def default_params(self) -> EnvParams:
        return self.make_params()

    def make_params(
        self,
        weights: RewardWeights | None = None,
        price_year: int | None = None,
        traffic: str | float | None = None,
        profile: str | None = None,
        price_region: str | None = None,
        car_region: str | None = None,
    ) -> EnvParams:
        """Build the numeric parameter pytree.

        The keyword overrides select different bundled datasets without a new
        env (all results share one shape, so sweeps never recompile); the
        scenario subsystem (:mod:`repro.scenarios`) layers PV/tariff/seasonal
        arrays on top of the result with plain ``replace``.
        """
        cfg, lay = self.config, self.layout
        profile = profile or cfg.scenario
        prices = datasets.price_profile(
            price_region or cfg.price_region, price_year or cfg.price_year, cfg.dt_minutes
        )
        arrivals = datasets.arrival_rate_curve(
            profile, traffic if traffic is not None else cfg.traffic, cfg.dt_minutes
        )
        cars = datasets.car_table(car_region or cfg.car_region)
        user = datasets.user_profile_params(profile)
        stay_mean, stay_sigma = user["stay"]
        # lognormal: E[X] = exp(mu + sigma^2/2) -> mu = log(mean) - sigma^2/2
        stay_mu_log = float(np.log(stay_mean) - 0.5 * stay_sigma**2)

        # battery column participates in the root constraint only
        batt_col = np.zeros((lay.n_nodes, 1), dtype=np.float32)
        if lay.battery.enabled:
            batt_col[0, 0] = 1.0
        member = np.concatenate([lay.member, batt_col], axis=1)

        b = lay.battery
        benabled = float(b.enabled)
        p = EnvParams(
            member=jnp.asarray(member),
            node_budget=jnp.asarray(lay.node_limit * lay.node_eff),
            evse_voltage=jnp.asarray(lay.evse_voltage),
            evse_max_current=jnp.asarray(lay.evse_max_current),
            evse_path_eff=jnp.asarray(lay.evse_path_eff),
            evse_is_dc=jnp.asarray(lay.evse_is_dc),
            evse_mask=jnp.asarray(lay.mask),
            evse_v2g_mask=jnp.asarray(lay.mask),  # default: every real lane
            #   is bidirectional hardware; scenarios lower a fraction instead
            batt_voltage=jnp.float32(b.voltage),
            batt_max_current=jnp.float32(b.max_current * benabled),
            batt_capacity=jnp.float32(b.capacity_kwh),
            batt_eff=jnp.float32(b.efficiency),
            batt_tau=jnp.float32(b.tau),
            batt_init_soc=jnp.float32(b.init_soc * benabled),
            price_buy_table=jnp.asarray(prices),
            arrival_rate=jnp.asarray(arrivals),
            arrival_day_scale=jnp.ones((datasets.DAYS_PER_YEAR,), jnp.float32),
            pv_kw_table=jnp.zeros(
                (datasets.DAYS_PER_YEAR, cfg.steps_per_day), jnp.float32
            ),
            grid_cap_kw_table=jnp.full(
                (datasets.DAYS_PER_YEAR, cfg.steps_per_day),
                GRID_CAP_UNLIMITED,
                jnp.float32,
            ),
            grid_setpoint_kw_table=jnp.zeros(
                (datasets.DAYS_PER_YEAR, cfg.steps_per_day), jnp.float32
            ),
            car_probs=jnp.asarray(cars[:, 0]),
            car_capacity=jnp.asarray(cars[:, 1]),
            car_ac_kw=jnp.asarray(cars[:, 2]),
            car_dc_kw=jnp.asarray(cars[:, 3]),
            car_tau=jnp.asarray(cars[:, 4]),
            stay_mu_log=jnp.float32(stay_mu_log),
            stay_sigma=jnp.float32(stay_sigma),
            target_soc_mu=jnp.float32(user["target"][0]),
            target_soc_std=jnp.float32(user["target"][1]),
            soc0_a=jnp.float32(user["soc0"][0]),
            soc0_b=jnp.float32(user["soc0"][1]),
            p_time_sensitive=jnp.float32(user["p_time_sensitive"]),
            p_sell=jnp.float32(0.75),  # Table 3
            p_v2g_comp=jnp.float32(0.75),  # = p_sell: V2G spread off by default
            grid_sell_discount=jnp.float32(0.9),
            facility_cost=jnp.float32(3.0),  # EUR per hour (0.25 / 5-min step)
            demand_charge_rate=jnp.float32(0.0),  # flat tariff by default
            demand_contract_kw=jnp.float32(0.0),
            moer_scale=jnp.float32(0.4),
            grid_demand_amp=jnp.float32(20.0),
            weights=weights or RewardWeights(),
        )
        if cfg.fused_step:
            # hoist the kernel's lane-padded pole pack out of the per-step
            # path: built once here, carried through scenario lowering (which
            # only swaps tables/economics, never the electrical fields below)
            from repro.kernels.chargax_step import ops as fused_ops

            p = dataclasses.replace(p, pole=fused_ops.build_pole_params(p))
        return p

    # ------------------------------------------------------------------
    # Spaces (the typed source of truth; the integer properties below are
    # thin aliases kept for existing call sites)
    # ------------------------------------------------------------------
    @cached_property
    def action_space(self) -> spaces.MultiDiscrete:
        """N EVSE heads + 1 battery head (paper: battery = (N+1)-th pole),
        each with ``2 * discretization + 1`` levels."""
        return spaces.MultiDiscrete(
            np.full((self.n_evse + 1,), 2 * self.config.discretization + 1)
        )

    @cached_property
    def observation_space(self) -> spaces.Box:
        """Flat float32 observation.

        Layout (8 features per port since the V2G debt feature): ``8 * n_evse``
        port features [occupied, current/imax, soc, e_remain/cap, v2g_debt/cap,
        t_remain/spd, rhat/imax, user_type], 2 battery features, 4 time
        features, 3 price features — see :meth:`observe`.
        """
        n = self.n_evse
        return spaces.Box(-np.inf, np.inf, (8 * n + 2 + 4 + 3,))

    @property
    def num_action_heads(self) -> int:
        return self.action_space.shape[0]

    @property
    def num_actions_per_head(self) -> int:
        return self.action_space.num_categories

    @property
    def obs_dim(self) -> int:
        return self.observation_space.shape[0]

    # ------------------------------------------------------------------
    # Reset / step
    # ------------------------------------------------------------------
    def reset(
        self, key: jax.Array, params: EnvParams | None = None
    ) -> tuple[jnp.ndarray, EnvState]:
        params = params if params is not None else self.default_params
        n = self.n_evse
        k_day, _ = jax.random.split(key)
        # exploring-starts over the price dataset (paper App. B.1): pick a day
        day = jax.random.randint(k_day, (), 0, params.price_buy_table.shape[0])
        zf = jnp.zeros((n,), jnp.float32)
        zi = jnp.zeros((n,), jnp.int32)
        state = EnvState(
            evse_current=zf,
            occupied=zf,
            soc=zf,
            e_remain=zf,
            v2g_debt=zf,
            batt_current=jnp.float32(0.0),
            batt_soc=params.batt_init_soc,
            t_remain=zi,
            rhat=zf,
            cap=zf,
            rbar=zf,
            tau=zf,
            user_type=zf,
            t=jnp.int32(0),
            day=day,
            price_buy=params.price_buy_table[day],
            profit_cum=jnp.float32(0.0),
            energy_delivered=jnp.float32(0.0),
            energy_discharged=jnp.float32(0.0),
            cars_served=jnp.float32(0.0),
            cars_rejected=jnp.float32(0.0),
            missing_kwh_cum=jnp.float32(0.0),
            overtime_steps_cum=jnp.float32(0.0),
        )
        return self.observe(state, params), state

    def step(
        self,
        key: jax.Array,
        state: EnvState,
        action: jnp.ndarray,
        params: EnvParams | None = None,
    ) -> TimeStep:
        """One transition = pure composition of the staged pipeline
        (:mod:`repro.core.transition`)::

            decode -> request -> allocate -> deliver -> depart_arrive
                   -> settle -> advance_time -> observe

        The ``request_stage`` / ``allocate`` / ``finish_step`` seams are
        public so :class:`repro.core.fleet.FleetEnv` can interpose a shared
        feeder-cap curtailment between the vmapped halves.

        With ``EnvConfig.fused_step`` on, the request/allocate/deliver
        stages route through the fused kernel package instead
        (:func:`repro.kernels.chargax_step.ops.fused_transition`); the
        settle tail is shared.
        """
        params = params if params is not None else self.default_params
        cfg = self.config
        if cfg.fused_step:
            from repro.kernels.chargax_step import ops as fused_ops

            with annotate("env/decode"):
                tgt_evse, tgt_batt = transition.decode(
                    params,
                    state,
                    action,
                    discretization=cfg.discretization,
                    allow_v2g=cfg.allow_v2g,
                    action_mode=cfg.action_mode,
                )
            with annotate("env/fused_transition"):
                alloc, charged = fused_ops.fused_transition(
                    params, state, tgt_evse, tgt_batt, cfg.dt_hours
                )
            return self.settle_tail(key, state, alloc, charged, params)
        applied = self.request_stage(state, action, params)
        with annotate("env/allocate"):
            alloc = transition.allocate(params, state, applied)
        return self.finish_step(key, state, alloc, params)

    def with_fused_step(self, fused: bool) -> "ChargaxEnv":
        """This env with the fused hot path on/off (self if already so)."""
        if self.config.fused_step == bool(fused):
            return self
        return ChargaxEnv(dataclasses.replace(self.config, fused_step=bool(fused)))

    def request_stage(
        self,
        state: EnvState,
        action: jnp.ndarray,
        params: EnvParams | None = None,
    ) -> transition.AppliedActions:
        """Pipeline stages decode + request: action -> constrained currents."""
        params = params if params is not None else self.default_params
        cfg = self.config
        with annotate("env/decode"):
            tgt_evse, tgt_batt = transition.decode(
                params,
                state,
                action,
                discretization=cfg.discretization,
                allow_v2g=cfg.allow_v2g,
                action_mode=cfg.action_mode,
            )
        with annotate("env/apply_actions"):
            return transition.request(params, state, tgt_evse, tgt_batt, cfg.dt_hours)

    def finish_step(
        self,
        key: jax.Array,
        state: EnvState,
        alloc: AllocationResult,
        params: EnvParams | None = None,
        arrival_rate_extra: jnp.ndarray | None = None,
    ) -> TimeStep:
        """Pipeline stages deliver -> depart_arrive -> settle -> advance_time
        -> observe, from an :class:`AllocationResult` (``state`` is the
        pre-step state the allocation was computed against).

        ``arrival_rate_extra`` (scalar, cars/step) adds to the Poisson arrival
        rate this step — the seam through which the city demand-allocation
        layer (:mod:`repro.city`) turns arrival rates into a per-station input
        computed from the population stream instead of a fixed table.
        """
        params = params if params is not None else self.default_params
        with annotate("env/charge_cars"):
            charged = transition.deliver(
                params, state, alloc.applied, self.config.dt_hours
            )
        return self.settle_tail(key, state, alloc, charged, params, arrival_rate_extra)

    def settle_tail(
        self,
        key: jax.Array,
        state: EnvState,
        alloc: AllocationResult,
        charged: transition.ChargeResult,
        params: EnvParams | None = None,
        arrival_rate_extra: jnp.ndarray | None = None,
    ) -> TimeStep:
        """Pipeline tail shared by the staged and fused routes:
        depart_arrive -> settle -> advance_time -> observe, from an already
        delivered :class:`ChargeResult`."""
        params = params if params is not None else self.default_params
        cfg = self.config
        dt = cfg.dt_hours
        with annotate("env/depart_arrive"):
            moved = transition.depart_arrive(
                params, charged.state, key, arrival_rate_extra
            )
        with annotate("env/reward"):
            settled = transition.settle(params, state, alloc, charged, moved, dt)
        new_state = transition.advance_time(params, moved.state, settled.profit)
        done = new_state.t >= cfg.episode_steps
        pen = settled.penalties
        info = {
            "profit": settled.profit,
            "reward": settled.reward,
            "e_net": settled.energies.e_net,
            "e_grid_net": settled.energies.e_grid_net,
            "e_pv": settled.energies.e_pv,
            "constraint_excess": pen.constraint,
            "missing_kwh": pen.satisfaction_time,
            "overtime_steps": moved.overtime_steps,
            "rejected": pen.rejected,
            "arrived": moved.n_arrived.astype(jnp.float32),
            "price_buy": settled.p_buy,
            # per-step KPI scalars for the obs metrics accumulators (unused
            # outputs are DCE'd by XLA, so consumers that ignore them pay
            # nothing): kWh into / out of cars this step, open V2G debt
            "energy_delivered": jnp.sum(jnp.maximum(charged.e_car, 0.0)),
            "energy_discharged": jnp.sum(jnp.maximum(-charged.e_car, 0.0)),
            "v2g_debt": jnp.sum(new_state.v2g_debt),
            # grid-coupling KPIs (kW): station draw vs the feeder envelope
            "grid/power_drawn": alloc.power_kw,
            "grid/cap": alloc.cap_kw,
            "grid/violation": alloc.violation_kw,
            "grid/setpoint_dev": settled.setpoint_dev_kw,
        }
        with annotate("env/observe"):
            obs = self.observe(new_state, params, label_price_window=True)
        return TimeStep(obs, new_state, settled.reward, done, info)

    # ------------------------------------------------------------------
    # Observation
    # ------------------------------------------------------------------
    def observe(
        self, state: EnvState, params: EnvParams, label_price_window: bool = False
    ) -> jnp.ndarray:
        cfg = self.config
        spd = cfg.steps_per_day
        return transition.observe(
            params,
            state,
            steps_per_day=spd,
            horizon_steps=max(int(cfg.obs_price_horizon_hours * spd / 24), 1),
            near_steps=max(int(spd / 24), 1),
            label_price_window=label_price_window,
        )


def make_baseline_max_action(env: ChargaxEnv):
    """Deprecated alias — moved to :func:`repro.rl.baselines.make_baseline_max_action`.

    Policy code does not belong in the physics module; import from
    ``repro.rl.baselines`` (or use ``BASELINES['max_charge']``).
    """
    import warnings

    warnings.warn(
        "repro.core.make_baseline_max_action is deprecated; import it from "
        "repro.rl.baselines (or use rl.baselines.BASELINES['max_charge'])",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.rl.baselines import make_baseline_max_action as _impl

    return _impl(env)
