"""FleetEnv — heterogeneous multi-station fleets under one vmap (ROADMAP:
"as many scenarios as you can imagine", "as fast as the hardware allows").

A fleet is a set of stations with *different* electrical architectures
(varying ``n_evse``/``n_nodes``) and possibly different scenarios.  Each
station's :class:`StationLayout` is padded to the fleet-wide maximum shape
(:func:`repro.core.station.pad_layout`) so every station's parameter pytree
has identical array shapes; the stacked parameters then run under a single
``jax.vmap`` of the ordinary :meth:`ChargaxEnv.step` — one compiled program
for the whole fleet, one jit cache entry regardless of fleet composition.

Padding is inert by construction: padded lanes are masked out of arrivals
(``EnvParams.evse_mask``), contribute zero current/energy, and — because
arrival randomness is folded per port index — each fleet lane is bit-for-bit
the single-station ``ChargaxEnv`` run at the same padded shape, and matches
an *unpadded* run exactly on discrete fields / to last-ulp float tolerance
on continuous ones (different compiled programs may round the Eq. 5 load
reduction differently; see ``tests/core/test_fleet.py``).

When a mesh is active (``jax.sharding.set_mesh``) the station
axis of ``reset``/``step`` outputs is constrained onto the mesh's data axes
(``repro.distributed.env_sharding``), so a fleet rollout shards across
devices with zero changes at the call site; without a mesh the constraint is
the identity and all single-device tests run unmodified.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core import station, transition
from repro.core.env import ChargaxEnv, EnvConfig
from repro.core.state import EnvParams, EnvState, RewardWeights
from repro.distributed import env_sharding
from repro.utils import stack_pytrees

# the one shared pytree-stacking helper (repro.utils.stack_pytrees): fleets
# stack a station axis, the scenario subsystem stacks a scenario axis —
# both names resolve to the same function
stack_params = stack_pytrees


class FleetEnv:
    """A fleet of heterogeneous charging stations stepped as one batch.

    Args:
        architectures: station architecture names (keys of
            ``station.ARCHITECTURES``), one per fleet member.
        config: shared static configuration (timing, action space, ...).
            Its ``architecture`` field is ignored; per-station architectures
            come from ``architectures``.
        scenarios: optional per-station scenarios — each entry is ``None``
            (use ``config``'s datasets), a scenario name, or a
            ``repro.scenarios.Scenario``.  Applied as pure array swaps on the
            padded per-station params.
        weights: reward weights shared by the fleet.
        couple_grid: step the fleet through the staged-pipeline seams with a
            *shared feeder power envelope*: after the vmapped
            decode/request/allocate stages, the stations' post-allocation
            grid draws are summed and proportionally curtailed against the
            fleet cap — station 0's ``grid_cap_kw_table`` read at station 0's
            clock (the fleet-level grid axis; give every station the same
            table via a shared scenario) — before the vmapped deliver/settle
            stages resume.  Pure array ops between two vmapped halves, so the
            one-jit-entry invariant survives; with the default unlimited cap
            the coupled step is bit-identical to the uncoupled vmap.
            Fleet-excess kW are attributed to stations pro-rata by draw on
            top of their local ``grid/violation``.
        city: couple the fleet to a city-scale arrival stream — a
            :class:`repro.city.CityParams` (or a scenario/name whose
            ``city_*`` axis builds one): each step, the population stream at
            the fleet clock is split across stations by the gravity/queue
            choice model (:mod:`repro.city.demand`) and fed into the vmapped
            finish as a per-station arrival-rate input on top of each
            station's own table.  ``info`` gains ``city/arrival_rate`` (S,),
            plus broadcast ``city/overflow``/``city/stream``.  A zero
            population adds exactly zero rate, leaving the coupled fleet
            bit-identical to the uncoupled one.

    ``reset``/``step`` mirror the single-station API with a leading station
    axis: obs ``(S, obs_dim)``, reward ``(S,)``, action ``(S, heads)``.
    ``info`` carries per-station entries plus fleet-aggregated
    ``fleet_reward``/``fleet_profit``; every info leaf is uniformly ``(S,)``
    (aggregates are broadcast), so ``tree_map``-based auto-reset/stacking
    works when the fleet is nested under an outer vmap or scan.

    ``shard=True`` (default) constrains the station axis of all outputs onto
    the ambient mesh's data axes — a no-op on a single device.
    """

    def __init__(
        self,
        architectures: Sequence[str],
        config: EnvConfig | None = None,
        scenarios: Sequence[Any] | None = None,
        weights: RewardWeights | None = None,
        shard: bool = True,
        couple_grid: bool = False,
        city: Any | None = None,
    ):
        if not architectures:
            raise ValueError("fleet needs at least one station")
        if scenarios is not None and len(scenarios) != len(architectures):
            raise ValueError("need one scenario entry per station")
        base = config or EnvConfig()
        if city is not None:
            from repro.city.params import CityParams, make_city

            if not isinstance(city, CityParams):
                # scenario name / Scenario: build its city axis for this fleet
                city = make_city(
                    city, n_stations=len(architectures), dt_minutes=base.dt_minutes
                )
            if city.n_stations != len(architectures):
                raise ValueError(
                    f"city has {city.n_stations} stations, fleet has "
                    f"{len(architectures)}"
                )
        self.city = city
        self.architectures = tuple(architectures)
        self.scenarios = tuple(scenarios) if scenarios is not None else None

        # probe the unpadded layouts to find the fleet-wide padded shape
        layouts = [station.ARCHITECTURES[a]() for a in architectures]
        self.max_evse = max(l.n_evse for l in layouts)
        self.max_nodes = max(l.n_nodes for l in layouts)
        self.envs = [
            ChargaxEnv(
                dataclasses.replace(
                    base,
                    architecture=a,
                    pad_evse=self.max_evse,
                    pad_nodes=self.max_nodes,
                )
            )
            for a in architectures
        ]
        # all stations share one padded template: the first env's pure
        # reset/step close over only static config, shared fleet-wide
        self.template = self.envs[0]
        self.config = self.template.config
        self.weights = weights
        self.shard = shard
        self.couple_grid = couple_grid
        self._v_reset = jax.vmap(self.template.reset, in_axes=(0, 0))
        self._v_step = jax.vmap(self.template.step, in_axes=(0, 0, 0, 0))
        # staged-pipeline seams for the grid-coupled step
        self._v_request = jax.vmap(self.template.request_stage, in_axes=(0, 0, 0))
        self._v_allocate = jax.vmap(transition.allocate, in_axes=(0, 0, 0))
        self._v_finish = jax.vmap(self.template.finish_step, in_axes=(0, 0, 0, 0))
        # city coupling: finish_step with a per-station arrival-rate input —
        # the fixed arrival table becomes one component of a dynamic rate
        self._v_finish_rate = jax.vmap(
            lambda k, s, a, p, r: self.template.finish_step(
                k, s, a, p, arrival_rate_extra=r
            ),
            in_axes=(0, 0, 0, 0, 0),
        )

    def with_fused_step(self, fused: bool) -> "FleetEnv":
        """This fleet with the fused hot path toggled on every station.

        The uncoupled vmapped step routes through the fused kernel wholesale;
        the grid-/city-coupled step keeps its staged seams (the shared-feeder
        curtailment interposes between vmapped halves) — see docs/kernels.md.
        """
        if self.config.fused_step == bool(fused):
            return self
        return FleetEnv(
            self.architectures,
            dataclasses.replace(self.config, fused_step=bool(fused)),
            self.scenarios,
            self.weights,
            self.shard,
            self.couple_grid,
            self.city,
        )

    def _constrain(self, tree):
        """Pin the station axis to the ambient mesh's data axes (no-op when
        no mesh is active or ``shard=False``)."""
        if not self.shard:
            return tree
        return env_sharding.constrain_env_batch(tree)

    # ------------------------------------------------------------------
    @property
    def n_stations(self) -> int:
        return len(self.envs)

    @property
    def num_action_heads(self) -> int:
        return self.template.num_action_heads

    @property
    def num_actions_per_head(self) -> int:
        return self.template.num_actions_per_head

    @property
    def obs_dim(self) -> int:
        return self.template.obs_dim

    @cached_property
    def default_params(self) -> EnvParams:
        """Stacked (S, ...) parameter pytree, one slice per station."""
        if self.scenarios is None:
            return stack_params(
                [env.make_params(weights=self.weights) for env in self.envs]
            )
        # any scenario in the fleet -> lower EVERY station through the
        # scenario path (None becomes the config's own world) so all slices
        # share the scenario-normalised array shapes (padded car tables,
        # drift tables) and stack cleanly
        from repro import scenarios as _scen

        cfg = self.config
        baseline = _scen.Scenario(
            name="__config__",
            profile=cfg.scenario,
            traffic=cfg.traffic,
            price_region=cfg.price_region,
            price_year=cfg.price_year,
            car_region=cfg.car_region,
        )
        params = []
        for i, env in enumerate(self.envs):
            sc = self.scenarios[i]
            if sc is None:
                sc = baseline
            elif isinstance(sc, str):
                sc = _scen.make(sc)
            params.append(sc.make_params(env, weights=self.weights))
        return stack_params(params)

    def station_params(self, i: int, params: EnvParams | None = None) -> EnvParams:
        """Slice station ``i``'s (unstacked) params back out of the fleet."""
        params = params if params is not None else self.default_params
        return jax.tree_util.tree_map(lambda x: x[i], params)

    def sample_action(self, key: jax.Array) -> jnp.ndarray:
        return jax.random.randint(
            key,
            (self.n_stations, self.num_action_heads),
            0,
            self.num_actions_per_head,
        )

    # ------------------------------------------------------------------
    def reset(
        self, key: jax.Array, params: EnvParams | None = None
    ) -> tuple[jnp.ndarray, EnvState]:
        params = params if params is not None else self.default_params
        keys = jax.random.split(key, self.n_stations)
        obs, state = self._v_reset(keys, params)
        return self._constrain(obs), self._constrain(state)

    def step(
        self,
        key: jax.Array,
        state: EnvState,
        action: jnp.ndarray,  # (S, heads) int32
        params: EnvParams | None = None,
    ) -> tuple[jnp.ndarray, EnvState, jnp.ndarray, jnp.ndarray, dict]:
        return self.step_with_city(key, state, action, params, self.city)

    def step_with_city(
        self,
        key: jax.Array,
        state: EnvState,
        action: jnp.ndarray,  # (S, heads) int32
        params: EnvParams | None = None,
        city=None,
    ) -> tuple[jnp.ndarray, EnvState, jnp.ndarray, jnp.ndarray, dict]:
        """``step`` with the city passed as a *traced argument* — the seam the
        placement sweep (:func:`repro.city.sweep_layouts`) vmaps over to score
        a stack of candidate ``CityParams`` under one compiled program."""
        params = params if params is not None else self.default_params
        keys = jax.random.split(key, self.n_stations)
        if self.couple_grid or city is not None:
            obs, state, reward, done, info = self._staged_step(
                keys, state, action, params, city
            )
        else:
            obs, state, reward, done, info = self._v_step(keys, state, action, params)
        info = dict(info)
        # fleet aggregates broadcast to (S,) so every info leaf has a uniform
        # leading station axis — tree_map stacking under an outer vmap/scan
        # would otherwise see mixed () / (S,) shapes and fail
        info["fleet_reward"] = jnp.broadcast_to(jnp.sum(reward), reward.shape)
        info["fleet_profit"] = jnp.broadcast_to(jnp.sum(info["profit"]), reward.shape)
        obs, state, reward, done, info = self._constrain(
            (obs, state, reward, done, info)
        )
        return obs, state, reward, done, info

    def _staged_step(self, keys, state, action, params, city=None):
        """Fleet-coupled step through the staged-pipeline seams.

        Grid coupling: shared feeder curtailment between the vmapped
        request/allocate and deliver/settle halves.  City coupling: the
        population arrival stream is allocated across stations
        (:mod:`repro.city.demand`) from the pre-step state and fed into the
        vmapped finish as a per-station arrival-rate input; a zero population
        contributes exactly zero rate, so the coupled fleet stays
        bit-identical to the uncoupled one (``tests/city/``)."""
        applied = self._v_request(state, action, params)
        alloc = self._v_allocate(params, state, applied)  # per-station caps
        if self.couple_grid:
            # fleet feeder cap: station 0's grid table at station 0's clock
            # (all stations share the episode clock; days differ only across
            # resets)
            cap_table = params.grid_cap_kw_table[0]
            fleet_cap = cap_table[
                jnp.mod(state.day[0], cap_table.shape[0]),
                jnp.mod(state.t[0], cap_table.shape[1]),
            ]
            p = alloc.power_kw  # (S,) post-local-allocation draws
            total = jnp.sum(p)
            scale = jnp.minimum(1.0, fleet_cap / jnp.maximum(total, 1e-9))
            fleet_excess = jnp.maximum(total - fleet_cap, 0.0)
            share = p / jnp.maximum(total, 1e-9)  # pro-rata attribution
            alloc = transition.AllocationResult(
                applied=jax.vmap(transition.curtail, in_axes=(0, None))(
                    alloc.applied, scale
                ),
                power_req_kw=alloc.power_req_kw,
                power_kw=p * scale,
                cap_kw=jnp.minimum(alloc.cap_kw, fleet_cap),
                violation_kw=alloc.violation_kw + fleet_excess * share,
            )
        if city is None:
            return self._v_finish(keys, state, alloc, params)

        from repro.city import demand

        calloc, stream = demand.city_rates(city, params, state)
        # the stream split respects the station-axis sharding: rates carry a
        # leading (S,) axis, constrained onto the mesh's data axes like every
        # other per-station tensor (no-op on a single device)
        rates = self._constrain(calloc.rates)
        obs, new_state, reward, done, info = self._v_finish_rate(
            keys, state, alloc, params, rates
        )
        info = dict(info)
        info["city/arrival_rate"] = calloc.rates
        info["city/overflow"] = jnp.broadcast_to(calloc.overflow, reward.shape)
        info["city/stream"] = jnp.broadcast_to(stream, reward.shape)
        return obs, new_state, reward, done, info
