import os
import sys

if "--dryrun" in sys.argv:  # must precede ANY jax import (device-count lock)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Chargax PPO at pod scale — the paper's technique as a first-class feature.

Two modes:
  * real training (any device count):   python -m repro.launch.rl_train
  * production-mesh dry-run (512 dev):  python -m repro.launch.rl_train --dryrun

The dry-run lowers ONE full PPO update (rollout scan + GAE + minibatch
epochs) with the environment batch sharded across the data axes of the
16x16 / 2x16x16 meshes — the paper-representative cell of EXPERIMENTS.md
§Roofline: on-device env steps mean rollouts never leave the chips, the
paper's core claim generalised to pods (DESIGN.md §3).
"""
import argparse
import contextlib
import json
import time

import jax
import numpy as np

from repro.analysis.hlo import collective_stats
from repro.core import ChargaxEnv, EnvConfig
from repro.distributed import env_sharding
from repro.rl import PPOConfig, make_train
from repro.utils import use_accurate_transcendentals, use_compile_cache

# env-batch constraint now lives in the distributed layer, shared with
# FleetEnv and the benchmarks
make_shard_envs = env_sharding.make_shard_envs


def run_dryrun(args) -> dict:
    from repro.launch.mesh import make_production_mesh

    results = []
    for multi_pod in (False, True):
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.devices.size
        env = ChargaxEnv(
            EnvConfig(
                scenario=args.scenario, traffic=args.traffic, fused_step=args.fused
            )
        )
        cfg = PPOConfig(
            num_envs=args.num_envs * n_dev,
            rollout_steps=args.rollout,
            total_timesteps=args.num_envs * n_dev * args.rollout,  # 1 update
            num_minibatches=4,
            hidden=(128, 128),
        )
        with jax.sharding.set_mesh(mesh):
            train = make_train(cfg, env, shard_envs=make_shard_envs(mesh))
            t0 = time.perf_counter()
            lowered = jax.jit(train).lower(jax.random.key(0))
            compiled = lowered.compile()
            wall = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        rec = {
            "cell": "chargax-ppo-update",
            "mesh": "2x16x16" if multi_pod else "16x16",
            "num_envs": cfg.num_envs,
            "rollout_steps": cfg.rollout_steps,
            "compile_s": round(wall, 2),
            "bytes_per_device": int(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0)
            ),
            "hlo_flops": float(cost.get("flops", -1)),
            "hlo_bytes": float(cost.get("bytes accessed", -1)),
            "collectives": collective_stats(compiled.as_text()),
            "ok": True,
        }
        print(json.dumps(rec, indent=1))
        results.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    return results


def _expand_scenarios(spec: str) -> list[str]:
    """Expand ``--scenarios`` tokens: names pass through, pack names
    (``REAL_PACK``, ``GRID_PACK``, ``CITY_PACK``, ``V2G_PACK``, ``V2G_MIXED_PACK``,
    ``CATALOG``) expand to
    their members — so ``--scenarios REAL_PACK,shopping_flat`` trains across
    the real-data worlds plus the synthetic baseline in one distribution."""
    from repro import scenarios as _scen

    packs = {
        "REAL_PACK": _scen.REAL_PACK,
        "GRID_PACK": _scen.GRID_PACK,
        "CITY_PACK": _scen.CITY_PACK,
        "V2G_PACK": _scen.V2G_PACK,
        "V2G_MIXED_PACK": _scen.V2G_MIXED_PACK,
        "CATALOG": tuple(s.name for s in _scen.CATALOG),
    }
    names: list[str] = []
    for tok in spec.split(","):
        tok = tok.strip()
        names.extend(packs.get(tok, (tok,)))
    return names


def _profile_probe(args, cfg, env, shard_envs, scenario_params, obs):
    """Emit a perfetto-viewable trace of ONE representative PPO update.

    The real training run stays untraced (the CPU tracer records every op
    execution — tracing thousands of updates produces multi-GB buffers and
    a multi-minute flush).  Every update executes the same compiled program,
    so one update IS the profile.  Inside the session:

      * trace+lower+compile of the probe happens with annotations ON, so
        the host timeline carries the named phase spans (``env/*``,
        ``wrap/*``, ``ppo/*``) nested exactly as the program is structured;
      * one update executes with minimal loop trip counts (short rollout,
        one epoch/minibatch — op set identical, fewer repeated events), so
        the device timeline shows the runtime op mix.
    """
    probe_rollout = min(args.rollout, 8)
    probe_cfg = PPOConfig(
        total_timesteps=cfg.num_envs * probe_rollout,
        num_envs=cfg.num_envs,
        rollout_steps=probe_rollout,
        num_minibatches=1,
        update_epochs=1,
        hidden=cfg.hidden,
    )
    probe = make_train(
        probe_cfg, env, shard_envs=shard_envs, scenario_params=scenario_params
    )
    key = jax.random.key(args.seed)
    with obs.trace_session(args.profile, keep_xplane=False):
        with obs.annotate("profile/trace_and_compile"):
            compiled = jax.jit(probe).lower(key).compile()
        with obs.annotate("profile/run_one_update"):
            pout = compiled(key)
            jax.block_until_ready(pout["metrics"]["rollout_reward"])


def run_train(args):
    from repro import obs

    env = ChargaxEnv(
        EnvConfig(
            scenario=args.scenario,
            traffic=args.traffic,
            allow_v2g=args.v2g,
            fused_step=args.fused,
        )
    )
    if args.fused:
        from repro.kernels.chargax_step.ops import resolve_impl

        print(f"[ppo] fused step kernel ON (impl={resolve_impl()})")
    # typed env surface (repro.envs): PPO wraps this in
    # LogWrapper(AutoReset(VmapWrapper)) with on-device KPI accumulation
    print(f"[ppo] obs={env.observation_space} actions={env.action_space}")
    cfg = PPOConfig(
        total_timesteps=args.timesteps,
        num_envs=args.num_envs,
        rollout_steps=args.rollout,
    )
    scenario_names = _expand_scenarios(args.scenarios) if args.scenarios else None
    if args.v2g and scenario_names is None:
        # default --v2g distribution: V2G-heavy worlds mixed with their
        # charge-only counterparts (per-port v2g masks are plain arrays, so
        # the mix still compiles once)
        from repro.scenarios import V2G_MIXED_PACK

        # largest pack prefix that divides num_envs (nested vmap needs an
        # even envs-per-scenario split)
        n_scen = max(
            s for s in range(1, len(V2G_MIXED_PACK) + 1) if args.num_envs % s == 0
        )
        scenario_names = list(V2G_MIXED_PACK[:n_scen])
        print(f"[ppo] --v2g default mix: {','.join(scenario_names)}")
    scenario_params = None
    if scenario_names:
        from repro import scenarios as _scen

        per_scenario = [_scen.make(n).make_params(env) for n in scenario_names]
        scenario_params = _scen.stack_params(per_scenario)
        print(
            f"[ppo] training across {len(scenario_names)} scenarios "
            "(one table copy each)"
        )
        if args.preflight:
            # recompile sentinel: every selected scenario must reuse ONE
            # compiled step (pure array swaps) — seconds to check here vs
            # minutes of silently duplicated training compiles later
            obs.assert_one_compiled_step(
                env, per_scenario, label=f"scenarios {','.join(scenario_names)}"
            )
            print(
                f"[obs] preflight: {len(per_scenario)} scenarios share one "
                "compiled step (no recompiles)"
            )

    # multi-device: shard the env batch over a data mesh built from every
    # visible device; single device degrades to no mesh / no constraints
    n_dev = jax.device_count()
    mesh_ctx = contextlib.nullcontext()
    shard_envs = None
    if n_dev > 1:
        if cfg.num_envs % n_dev:
            raise ValueError(
                f"--num-envs {cfg.num_envs} is not divisible by the {n_dev} "
                "visible devices: the env batch shards evenly over all of them"
            )
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        mesh_ctx = jax.sharding.set_mesh(mesh)
        shard_envs = env_sharding.make_shard_envs(mesh)
        print(f"[ppo] sharding {cfg.num_envs} envs over {n_dev} devices")

    steps = cfg.num_updates * cfg.batch_size
    with mesh_ctx:
        train = jax.jit(
            make_train(cfg, env, shard_envs=shard_envs, scenario_params=scenario_params)
        )
        key = jax.random.key(args.seed)
        t0 = time.perf_counter()
        compiled = train.lower(key).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        print(
            f"[ppo] compiled in {compile_s:.1f}s | device bytes: "
            f"args={mem.argument_size_in_bytes} out={mem.output_size_in_bytes} "
            f"temp={mem.temp_size_in_bytes}"
        )
        # every update runs inside the one compiled program: nothing may
        # compile once training starts
        with obs.compile_guard("rl_train updates"):
            t0 = time.perf_counter()
            out = compiled(key)
            jax.block_until_ready(out["metrics"]["rollout_reward"])
            wall = time.perf_counter() - t0
        if args.profile:
            _profile_probe(args, cfg, env, shard_envs, scenario_params, obs)
    rr = out["metrics"]["rollout_reward"]
    print(
        f"[ppo] {steps:,} steps in {wall:.1f}s "
        f"({steps/wall:,.0f} env-steps/s) | "
        f"reward first->last: {float(rr[0]):.1f} -> {float(rr[-1]):.1f}"
    )
    kpis = {
        k.split("/", 1)[1]: float(np.asarray(v)[-1])
        for k, v in out["metrics"].items()
        if k.startswith("kpi/")
    }
    if kpis:
        print(
            "[kpi] last update, per env-step: "
            + " ".join(f"{k}={v:.3f}" for k, v in sorted(kpis.items()))
        )
    if args.profile:
        trace = obs.latest_trace(args.profile)
        print(
            f"[obs] profile trace: {trace} "
            "(open at https://ui.perfetto.dev — phases env/*, wrap/*, ppo/*)"
        )
    writer = None
    if args.metrics_out:
        from repro.kernels.chargax_step.ops import resolve_impl

        writer = obs.MetricsWriter(
            args.metrics_out,
            run="rl_train",
            scenario=args.scenario,
            scenarios=scenario_names,
            timesteps=args.timesteps,
            num_envs=cfg.num_envs,
            seed=args.seed,
            fused_step=args.fused,
            fused_impl=resolve_impl() if args.fused else None,
        )
        writer.write(
            {
                "compile_s": round(compile_s, 2),
                "wall_s": round(wall, 2),
                "env_steps_per_sec": round(steps / wall, 1),
                "rollout_reward_first": float(rr[0]),
                "rollout_reward_last": float(rr[-1]),
                "episode_return_last": float(
                    np.asarray(out["metrics"]["episode_return"])[-1]
                ),
                **{f"kpi/{k}": v for k, v in kpis.items()},
            },
            kind="train",
        )
    if args.v2g and scenario_names:
        # discharge/degradation report: trained agent vs the always-max and
        # arbitrage baselines on the first (V2G-heavy) scenario of the mix
        from repro import scenarios as _scen
        from repro.rl import evaluate, make_ppo_policy
        from repro.rl.baselines import max_charge_policy, v2g_arbitrage_policy

        sc_params = _scen.make(scenario_names[0]).make_params(env)
        policies = {
            "ppo": (make_ppo_policy(env), out["runner_state"].params),
            "max_charge": (max_charge_policy(env), None),
            "v2g_arbitrage": (v2g_arbitrage_policy(env, sc_params), None),
        }
        for name, (pol, pol_params) in policies.items():
            res = evaluate(
                env, pol, pol_params, jax.random.key(17), 16, env_params=sc_params,
                writer=writer, tag=f"{scenario_names[0]}/{name}",
            )
            print(
                f"[v2g eval] {scenario_names[0]} {name}: "
                f"profit={res['daily_profit']:.1f} "
                f"discharged={res['energy_discharged_kwh']:.1f}kWh "
                f"discharge_frac={res['v2g_discharge_frac']:.3f} "
                f"missing={res['missing_kwh']:.1f}kWh"
            )
    if writer is not None:
        writer.close()
        print(f"[obs] metrics JSONL: {writer.path}")
    return {**out, "compile_s": compile_s, "run_s": wall}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument(
        "--scenarios",
        default=None,
        help="comma-separated catalog scenarios to train across "
        "(nested-vmap distribution training; num-envs must be a multiple); "
        "pack names REAL_PACK / GRID_PACK / CITY_PACK / V2G_PACK / V2G_MIXED_PACK "
        "/ CATALOG expand",
    )
    ap.add_argument("--scenario", default="shopping")
    ap.add_argument("--traffic", default="medium")
    ap.add_argument(
        "--v2g",
        action="store_true",
        help="allow car discharging (EnvConfig.allow_v2g); without --scenarios "
        "this trains across the bundled mixed v2g/non-v2g pack",
    )
    ap.add_argument(
        "--fused",
        action="store_true",
        help="route the env step through the fused kernel hot path "
        "(EnvConfig.fused_step; Pallas on TPU, bit-exact jnp ref elsewhere; "
        "override with CHARGAX_FUSED_IMPL=pallas|interpret|ref)",
    )
    ap.add_argument("--timesteps", type=int, default=300_000)
    ap.add_argument("--num-envs", type=int, default=12)
    ap.add_argument("--rollout", type=int, default=300)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="results/ppo_dryrun.json")
    ap.add_argument(
        "--profile",
        default=None,
        metavar="DIR",
        help="write a perfetto-viewable trace of the training run to DIR "
        "(phases annotated: env/*, wrap/*, ppo/*; open at ui.perfetto.dev)",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="append run manifest + train/eval KPI records to a JSONL sink",
    )
    ap.add_argument(
        "--preflight",
        action=argparse.BooleanOptionalAction,
        default=True,
        help="with --scenarios: assert the catalog shares ONE compiled step "
        "before training (recompile sentinel); --no-preflight skips",
    )
    args = ap.parse_args(argv)
    if args.dryrun:
        return run_dryrun(args)
    use_accurate_transcendentals()
    use_compile_cache()
    return run_train(args)


if __name__ == "__main__":
    main()
