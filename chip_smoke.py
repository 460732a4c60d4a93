#!/usr/bin/env python
"""Bring-up check: the Chargax main path on a TPU, end to end.

    python chip_smoke.py                 # phases a-c on one chip
    python chip_smoke.py --four-chips    # data-parallel PPO on 4 chips vs 1

Default run, on the paper's 16-EVSE + battery station (``EnvConfig()``) at
4096 parallel stations:

  a. the staged env step under ``AutoReset(VmapWrapper)`` for one day on the
     chip; each step is checked against the same step recomputed on the host
     CPU from the chip's own pre-step state and key;
  b. the same steps through the fused Pallas kernel (``fused_step=True``),
     checked against (a), plus ``fused_step`` at its default block against
     the jnp reference ``ref.fused_step_ref``;
  c. PPO through ``repro.launch.rl_train.main``, staged and ``--fused``.

``--four-chips`` runs only ``rl_train``'s data-parallel path over four chips
and the same update on one chip, and compares them.

There is no CPU fallback: without a TPU the script exits non-zero before any
phase.  Every failed phase or comparison raises, so the process exits
non-zero; only a full pass prints the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache follows ``repro.utils.use_compile_cache``
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``), and
libtpu runs with ``repro.utils.use_accurate_transcendentals``, as under
``rl_train``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
N_ENVS = 4096
ROLLOUT = 300
PPO_SCENARIOS = "shopping_flat,shopping_pv_tou,shopping_fleet_drift,real_nl_2024_shopping_tou"
# float PPO metrics of a sharded vs an unsharded update (the tolerance of
# tests/distributed/test_env_sharding.py's PPO parity test)
PPO_RTOL, PPO_ATOL = 1e-4, 1e-5
# per-step fields of the day rollout that must agree exactly
DISCRETE = ("occupied", "t_remain", "arrived", "rejected")


def log(msg: str) -> None:
    print(msg, flush=True)


def _pin_one_chip() -> None:
    """Show this process one chip; must run before the TPU backend starts."""
    os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
    os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
    os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def _mem(compiled) -> str:
    m = compiled.memory_analysis()
    return (
        f"args={m.argument_size_in_bytes} out={m.output_size_in_bytes} "
        f"temp={m.temp_size_in_bytes} code={m.generated_code_size_in_bytes}"
    )


def _peak(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"


def _compile(jax, fn, *args, label: str):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    log(f"[{label}] compile {time.perf_counter() - t0:.2f}s | {_mem(compiled)}")
    return compiled


def _run(jax, compiled, *args, label: str):
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    log(f"[{label}] run {time.perf_counter() - t0:.2f}s | {_peak(jax)}")
    return out


def step_fn(venv):
    """(state, key, params) -> (next state, step outputs): one env step with a
    random action from ``venv.action_space``, both drawn from the key."""
    import jax

    def one_step(state, k, params):
        k_act, k_step = jax.random.split(k)
        ts = venv.step(k_step, state, venv.sample_action(k_act), params)
        return ts.state, {
            "obs": ts.obs,
            "reward": ts.reward,
            "profit": ts.info["profit"],
            "arrived": ts.info["arrived"],
            "rejected": ts.info["rejected"],
        }

    return one_step


def day_rollout(venv, n_steps: int):
    """key, params -> (step keys, pre-step states, per-step outputs, final
    state) of ``n_steps`` steps."""
    import jax

    one_step = step_fn(venv)

    def run(key, params):
        k_reset, k_run = jax.random.split(key)
        _, state = venv.reset(k_reset, params)
        keys = jax.random.split(k_run, n_steps)

        def body(s, k):
            ns, out = one_step(s, k, params)
            out = dict(out, occupied=ns.occupied > 0.5, t_remain=ns.t_remain,
                       soc=ns.soc, e_remain=ns.e_remain, batt_soc=ns.batt_soc)
            return ns, (s, out)

        final, (states, outs) = jax.lax.scan(body, state, keys)
        return keys, states, outs, final

    return run


def replay(venv):
    """states, keys, params -> (next states, step outputs) of every recorded
    step, each recomputed from its recorded pre-step state."""
    import jax

    one_step = step_fn(venv)

    def run(states, keys, params):
        return jax.lax.map(lambda sk: one_step(sk[0], sk[1], params), (states, keys))

    return run


def _mismatch(name: str, got, want):
    """Elementwise disagreement: discrete fields exactly, float fields past
    the kernel parity tolerance (``ref.PARITY_RTOL``/``PARITY_ATOL``)."""
    import numpy as np

    from repro.kernels.chargax_step.ref import PARITY_ATOL, PARITY_RTOL

    g, w = np.asarray(got), np.asarray(want)
    if name in DISCRETE or not np.issubdtype(w.dtype, np.floating):
        return g, w, g != w
    return g, w, ~np.isclose(g, w, rtol=PARITY_RTOL, atol=PARITY_ATOL)


def compare(label: str, got: dict, want: dict, env_axis: int, failures: list) -> None:
    """Compare the fields both sides have; record a failed comparison in
    ``failures`` so later phases still run (``main`` then exits non-zero)."""
    import numpy as np

    bad = []
    for k in sorted(got.keys() & want.keys()):
        g, w, off = _mismatch(k, got[k], want[k])
        n_off = int(off.sum())
        if n_off:
            first = tuple(np.argwhere(off)[0])
            diff = float(np.max(np.abs(g.astype(np.float64) - w.astype(np.float64))))
            envs = np.unique(np.argwhere(off)[:, env_axis]).size
            bad.append(k)
            log(
                f"[{label}] MISMATCH {k}: {n_off}/{off.size} elements in {envs} envs, "
                f"first at {[int(i) for i in first]} (got {g[first]}, want {w[first]}), "
                f"max |diff| {diff:.3e}"
            )
        else:
            log(f"[{label}] {k}: {g.size} elements agree")
    if bad:
        failures.append(f"{label}: fields disagree: {bad}")


def state_fields(state, imax) -> dict:
    """The state's fields, with ``rhat`` in the units of its one consumer,
    the observation's ``rhat / imax``.  Near a full pack rhat moves ~3600 A
    per unit of soc on the paper station, so one ulp of soc is 2e-4 A of
    rhat, the parity atol, but 1e-6 of rhat / imax."""
    import dataclasses

    fields = {f.name: getattr(state, f.name) for f in dataclasses.fields(state)}
    fields["rhat/imax"] = fields.pop("rhat") / imax
    return fields


def next_states(states, final):
    """Recorded pre-step states shifted by one: the state after each step."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda s, f: jnp.concatenate([s[1:], f[None]]), states, final
    )


def phase_staged(jax, n_envs: int, failures: list):
    """a. staged steps on the chip vs the same steps on the host CPU.

    Every chip step is recomputed on the CPU from the chip's own pre-step
    state and key, so no rounding carries over between steps: over a
    free-running day one boundary decided by the last bit (a charge
    curve's request crossing the departure threshold) would change the rest
    of that env's day."""
    from repro.core import ChargaxEnv, EnvConfig
    from repro.envs import AutoReset, VmapWrapper

    env = ChargaxEnv(EnvConfig())
    venv = AutoReset(VmapWrapper(env, n_envs))
    n_steps = env.config.episode_steps
    log(f"[a] staged rollout: {n_envs} envs x {n_steps} steps, paper_16 shopping/medium")
    run = day_rollout(venv, n_steps)
    key, params = jax.random.key(0), env.default_params
    compiled = _compile(jax, run, key, params, label="a/tpu")
    keys, states, outs, final = chip = _run(jax, compiled, key, params, label="a/tpu")

    cpu = jax.devices("cpu")[0]
    states_c, keys_c, params_c = jax.device_put((states, keys, params), cpu)
    compiled_c = _compile(jax, replay(venv), states_c, keys_c, params_c, label="a/cpu-replay")
    next_c, outs_c = _run(jax, compiled_c, states_c, keys_c, params_c, label="a/cpu-replay")
    assert {d.platform for d in jax.tree_util.tree_leaves(next_c)[0].devices()} == {"cpu"}
    compare("a step outputs", outs, outs_c, env_axis=1, failures=failures)
    imax = params.evse_max_current
    compare("a next states", state_fields(next_states(states, final), imax),
            state_fields(next_c, jax.device_put(imax, cpu)), env_axis=1, failures=failures)
    log(f"[a] done, {len(failures)} failed comparison(s) so far")
    return chip


def phase_fused(jax, n_envs: int, staged, failures: list):
    """b. the fused Pallas route on the chip vs (a), and the kernel vs its
    jnp reference at the kernel's default block."""
    from repro.core import ChargaxEnv, EnvConfig
    from repro.envs import AutoReset, VmapWrapper
    from repro.kernels.chargax_step import ops

    impl = ops.resolve_impl()
    if impl != "pallas":
        raise RuntimeError(
            f"fused step resolves to {impl!r} on this chip (is {ops.IMPL_ENV_VAR} "
            "set?); the smoke run checks the compiled Pallas kernel"
        )
    env = ChargaxEnv(EnvConfig(fused_step=True))
    venv = AutoReset(VmapWrapper(env, n_envs))
    n_steps = env.config.episode_steps
    params = env.default_params
    keys, states, outs, final = staged
    log(f"[b] fused steps (impl={impl}) from the staged chip states: {n_envs} envs x {n_steps} steps")
    compiled = _compile(jax, replay(venv), states, keys, params, label="b/replay")
    _count_kernels(compiled, "b/replay")
    next_f, outs_f = _run(jax, compiled, states, keys, params, label="b/replay")
    compare("b step outputs", outs_f, outs, env_axis=1, failures=failures)
    imax = params.evse_max_current
    compare("b next states", state_fields(next_f, imax),
            state_fields(next_states(states, final), imax), env_axis=1, failures=failures)
    del next_f

    # the kernel on a (n_envs, P) batch at its default block, vs the jnp ref
    mid = jax.tree_util.tree_map(lambda x: x[n_steps // 2], states)
    k1, k2 = jax.random.split(jax.random.key(1))
    te = jax.random.uniform(k1, (n_envs, env.n_evse), minval=-1.0, maxval=1.0) * params.evse_max_current
    tb = jax.random.uniform(k2, (n_envs,), minval=-1.0, maxval=1.0) * params.batt_max_current
    dt = env.config.dt_hours

    def kernel(impl):
        return lambda p, s, a, b: ops.fused_step(p, s, a, b, dt, impl=impl)

    compiled_k = _compile(jax, kernel("pallas"), params, mid, te, tb, label="b/kernel")
    _count_kernels(compiled_k, "b/kernel")
    got = _run(jax, compiled_k, params, mid, te, tb, label="b/kernel")
    want = jax.jit(kernel("ref"))(params, mid, te, tb)
    compare("b kernel vs ref", got._asdict(), want._asdict(), env_axis=0, failures=failures)
    log(f"[b] done, {len(failures)} failed comparison(s) so far")


def _exp_log_error(jax) -> str:
    """Largest difference of the chip's f32 exp and log from the host CPU's
    over a sweep of arguments, relative to max(|cpu value|, 1) (information
    only)."""
    import jax.numpy as jnp
    import numpy as np

    x = np.linspace(-20.0, 20.0, 1 << 16, dtype=np.float32)
    cpu = jax.devices("cpu")[0]
    parts = []
    for name, fn, arg in (("exp", jnp.exp, x), ("log", jnp.log, np.exp(x))):
        chip = np.asarray(jax.jit(fn)(arg), np.float64)
        host = np.asarray(jax.jit(fn)(jax.device_put(arg, cpu)), np.float64)
        rel = np.abs(chip - host) / np.maximum(np.abs(host), 1.0)
        parts.append(f"{name} max diff chip vs cpu {rel.max():.3e}")
    return ", ".join(parts)


def _count_kernels(compiled, label: str) -> None:
    n_calls = compiled.as_text().count("tpu_custom_call")
    log(f"[{label}] tpu_custom_call in compiled program: {n_calls}")
    if not n_calls:
        raise AssertionError(f"{label}: no tpu_custom_call, the Pallas kernel did not lower")


def _finite_metrics(label: str, metrics: dict) -> None:
    import numpy as np

    for k, v in sorted(metrics.items()):
        if not np.all(np.isfinite(np.asarray(v))):
            raise AssertionError(f"{label}: metric {k} is not finite: {v}")


def phase_ppo(jax, fused: bool):
    """c. PPO through rl_train.main: 2 updates of 4096 envs x 300 steps."""
    from repro.launch import rl_train

    label = "c/ppo-fused" if fused else "c/ppo-staged"
    argv = [
        "--num-envs", str(N_ENVS),
        "--rollout", str(ROLLOUT),
        "--timesteps", str(2 * N_ENVS * ROLLOUT),
        "--scenarios", PPO_SCENARIOS,
    ] + (["--fused"] if fused else [])
    log(f"[{label}] rl_train.main {' '.join(argv)}")
    out = rl_train.main(argv)
    metrics = jax.device_get(out["metrics"])
    _finite_metrics(label, metrics)
    steps = 2 * N_ENVS * ROLLOUT
    log(
        f"[{label}] compile {out['compile_s']:.2f}s run {out['run_s']:.2f}s "
        f"({steps / out['run_s']:.0f} env-steps/s, information only) | "
        f"loss={metrics['loss'].tolist()} reward={metrics['rollout_reward'].tolist()} | "
        f"{_peak(jax)}"
    )
    log(f"[{label}] PASS: finite losses/rewards, preflight passed, no compile after the first update")


def phase_four_chips(jax):
    """rl_train's data-parallel update over 4 chips vs the same update on one."""
    import numpy as np

    from repro.core import ChargaxEnv, EnvConfig
    from repro.launch import rl_train
    from repro.rl import PPOConfig, make_train

    n_dev = jax.device_count()
    if n_dev != 4:
        raise RuntimeError(f"--four-chips needs 4 chips, JAX sees {n_dev}")
    argv = ["--num-envs", str(N_ENVS), "--rollout", str(ROLLOUT), "--timesteps", str(N_ENVS * ROLLOUT)]
    log(f"[4chip] rl_train.main {' '.join(argv)}")
    out4 = rl_train.main(argv)
    batched = [
        x for x in jax.tree_util.tree_leaves(out4["runner_state"].env_state)
        if x.ndim and x.shape[0] == N_ENVS
    ]
    spans = sorted({len(x.sharding.device_set) for x in batched})
    log(f"[4chip] {len(batched)} env-batch leaves; devices per leaf: {spans}")
    if spans != [4]:
        raise AssertionError(f"env state does not span 4 devices: {spans}")

    env = ChargaxEnv(EnvConfig())
    cfg = PPOConfig(total_timesteps=N_ENVS * ROLLOUT, num_envs=N_ENVS, rollout_steps=ROLLOUT)
    one = jax.devices()[0]
    with jax.default_device(one):
        key = jax.random.key(0)
        compiled = _compile(jax, make_train(cfg, env), key, label="1chip")
        out1 = _run(jax, compiled, key, label="1chip")
    spans1 = {len(x.sharding.device_set) for x in jax.tree_util.tree_leaves(out1["runner_state"].env_state)}
    if spans1 != {1}:
        raise AssertionError(f"one-chip reference is not on one device: {spans1}")

    m4, m1 = jax.device_get(out4["metrics"]), jax.device_get(out1["metrics"])
    _finite_metrics("4chip", m4)
    for k in sorted(m1):
        a, b = np.asarray(m4[k]), np.asarray(m1[k])
        log(f"[4chip] {k}: 4 chips {a.tolist()} | 1 chip {b.tolist()}")
        np.testing.assert_allclose(a, b, rtol=PPO_RTOL, atol=PPO_ATOL, err_msg=k)
    log("[4chip] PASS: the 4-chip data-parallel update matches the one-chip update")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only rl_train's data-parallel update over 4 chips vs 1 chip",
    )
    args = ap.parse_args(argv)
    if not args.four_chips:
        _pin_one_chip()
    sys.path.insert(0, os.path.join(REPO, "src"))

    import jax

    from repro.utils import use_accurate_transcendentals, use_compile_cache

    libtpu_args = use_accurate_transcendentals()

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform!r}); nothing was run", file=sys.stderr)
        return 1

    from importlib import metadata

    cache_events: dict[str, int] = {}

    def count(event: str, **_) -> None:
        if event.startswith("/jax/compilation_cache/"):
            cache_events[event] = cache_events.get(event, 0) + 1

    jax.monitoring.register_event_listener(count)
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    log(
        f"device: {dev.platform} {dev.device_kind} x{jax.device_count()} | "
        f"jax {jax.__version__} jaxlib {metadata.version('jaxlib')} "
        f"libtpu {metadata.version('libtpu')} | compile cache {cache_dir}"
    )
    log(f"LIBTPU_INIT_ARGS={libtpu_args} | {_exp_log_error(jax)}")

    t0 = time.perf_counter()
    failures: list[str] = []
    if args.four_chips:
        phase_four_chips(jax)
    else:
        if jax.device_count() != 1:
            raise RuntimeError(f"default run must see one chip, JAX sees {jax.device_count()}")
        staged = phase_staged(jax, N_ENVS, failures)
        phase_fused(jax, N_ENVS, staged, failures)
        del staged
        phase_ppo(jax, fused=False)
        phase_ppo(jax, fused=True)
    hits = cache_events.get("/jax/compilation_cache/cache_hits", 0)
    misses = cache_events.get("/jax/compilation_cache/cache_misses", 0)
    log(f"compile cache: {hits} hits, {misses} misses | total {time.perf_counter() - t0:.1f}s")
    if failures:
        for f in failures:
            log(f"FAILED {f}")
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
