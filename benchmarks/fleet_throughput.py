"""Fleet throughput: heterogeneous multi-station rollouts under one vmap.

Measures env-steps/sec of a ``FleetEnv`` mixing three heterogeneous bundled
architectures (``paper_16``, ``deep_4x4``, ``single_dc_8``), each paired
with a different catalog scenario, replicated to fleets of increasing size —
the "millions of users" scaling axis of the ROADMAP.  A jitted 24h
``lax.scan`` rollout is timed per fleet size and a machine-readable JSON
summary line (``FLEET_JSON {...}``) is emitted for dashboards/CI.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import EnvConfig, FleetEnv
from repro.envs import FleetAdapter
from repro.obs import emit_json_line

ARCHS = ("paper_16", "deep_4x4", "single_dc_8")
SCENARIOS = ("shopping_pv_tou", "work_solar_summer", "highway_demand_charge")

LAST_SUMMARY: dict | None = None  # set by run(); persisted by benchmarks.run


def bench_fleet(n_replicas: int, n_days: int = 1, mesh=None) -> tuple[float, FleetEnv]:
    """Seconds for a jitted ``n_days``-day rollout of the replicated fleet.

    With ``mesh``, the stacked params/state are placed over its data axes and
    the rollout runs under the ambient mesh (``benchmarks.fleet_sharded``);
    without, this is the plain single-device harness.
    """
    import contextlib

    from repro.distributed import env_sharding, sharding

    fleet = FleetEnv(
        ARCHS * n_replicas,
        EnvConfig(),
        scenarios=SCENARIOS * n_replicas,
    )
    # the rollout drives the fleet through the Environment protocol: typed
    # action space, TimeStep returns
    env = FleetAdapter(fleet)
    steps = fleet.config.episode_steps * n_days

    with jax.sharding.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        params = env.default_params
        if mesh is not None:
            params = env_sharding.place_env_batch(params, mesh)

        @jax.jit
        def rollout(key, state):
            def body(carry, _):
                key, state = carry
                key, ka, ks = jax.random.split(key, 3)
                ts = env.step(ks, state, env.sample_action(ka), params)
                return (key, ts.state), jnp.sum(ts.reward)

            (_, state), rs = jax.lax.scan(body, (key, state), None, steps)
            return state, rs.sum()

        key = jax.random.key(0)
        _, state = env.reset(key, params)
        if mesh is not None:
            state = env_sharding.place_env_batch(state, mesh)
        state2, _ = rollout(key, state)  # compile
        jax.block_until_ready(state2.t)
        t0 = time.perf_counter()
        _, total = rollout(key, state)
        jax.block_until_ready(total)
    return time.perf_counter() - t0, fleet


def run(quick: bool = True):
    """Benchmark-harness entry point: list of (name, us_per_call, derived)."""
    global LAST_SUMMARY
    sizes = (1, 4) if quick else (1, 4, 16, 64)
    rows = []
    summary = []
    base_per_station = None  # smallest fleet's per-station throughput
    for n in sizes:
        secs, fleet = bench_fleet(n)
        steps = fleet.config.episode_steps * fleet.n_stations
        sps = steps / secs
        per_station = sps / fleet.n_stations
        if base_per_station is None:
            base_per_station = per_station
        # per-station throughput relative to the smallest fleet: 1.0 is
        # perfect linear scaling, < 1.0 makes the sub-linear falloff of
        # bigger vmapped fleets visible at a glance in BENCH_fleet.json
        eff = per_station / base_per_station
        rows.append(
            (
                f"fleet_{fleet.n_stations}_stations",
                secs * 1e6 / fleet.config.episode_steps,
                f"{sps:.0f} station-steps/s ({fleet.max_evse}-lane padded, "
                f"eff={eff:.2f})",
            )
        )
        summary.append(
            {
                "n_stations": fleet.n_stations,
                "architectures": list(fleet.architectures),
                "padded_evse": fleet.max_evse,
                "steps_per_sec": round(sps, 1),
                "seconds_per_24h_rollout": round(secs, 4),
                "scaling_efficiency": round(eff, 3),
            }
        )
    LAST_SUMMARY = {
        "num_envs": summary[-1]["n_stations"],
        "steps_per_sec": summary[-1]["steps_per_sec"],
        "scaling_efficiency": summary[-1]["scaling_efficiency"],
        "fleet_throughput": summary,
    }
    emit_json_line("FLEET_JSON", {"fleet_throughput": summary})
    return rows


if __name__ == "__main__":
    for row in run(quick=True):
        print(",".join(str(x) for x in row))
