"""Render §Dry-run / §Roofline tables from results/*.json into markdown,
plus the kernel-vs-reference speed table (ISSUE 10).

The kernel table times the SAME vmapped transition chain (request →
allocate → deliver, state threaded through a scan so XLA cannot hoist the
work) three ways:

  staged            — the lax pipeline ``env.step`` uses by default,
  fused_ref         — ``fused_transition`` on the jnp reference impl (the
                      CPU hot-path routing of ``EnvConfig.fused_step``),
  pallas_interpret  — the Pallas slab kernel in interpret mode (the only
                      way to exercise the kernel's lowering on CPU; its
                      absolute time is an emulation cost, not a perf claim
                      — on TPU the same kernel runs compiled).

Persisted by ``benchmarks.run`` as ``BENCH_roofline.json`` with
``fused_ref_vs_staged_frac`` in the summary; CI's bench-smoke job runs it.
"""
from __future__ import annotations

import json
import os
import time


def fmt_bytes(b):
    return f"{b/2**30:.2f}"


def dryrun_table(path="results/dryrun.json") -> str:
    if not os.path.exists(path):
        return "(dry-run results missing — run repro.launch.dryrun)"
    rows = json.load(open(path))
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["mesh"]))
    out = [
        "| arch | shape | mesh | ok | compile s | GiB/dev | fits 16G | collective GiB (once-counted) |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        coll = r.get("collectives", {}).get("total_bytes", 0)
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {'Y' if r.get('ok') else 'FAIL'} "
            f"| {r.get('compile_s','-')} | {fmt_bytes(r.get('bytes_per_device',0))} "
            f"| {'Y' if r.get('fits_16g_hbm') else 'tight'} | {fmt_bytes(coll)} |"
        )
    n_ok = sum(1 for r in rows if r.get("ok"))
    out.append(f"\n{n_ok}/{len(rows)} cells compile.")
    return "\n".join(out)


def roofline_table(path="results/roofline.json") -> str:
    if not os.path.exists(path):
        return "(roofline results missing — run repro.analysis.roofline)"
    rows = json.load(open(path))
    rows = [r for r in rows if "bottleneck" in r]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    out = [
        "| arch | shape | t_compute s | t_memory s | t_collective s | bound | "
        "model TFLOPs | useful ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} "
            f"| {r['t_collective_s']:.4f} | **{r['bottleneck']}** "
            f"| {r['model_flops']/1e12:.1f} | {r['useful_compute_ratio']:.2f} "
            f"| {r['roofline_fraction_compute']:.2f} |"
        )
    return "\n".join(out)


def bench_kernel_vs_reference(
    n_envs: int = 128, n_iters: int = 20, rounds: int = 3
) -> dict[str, float]:
    """Seconds per variant for ``n_iters`` chained transitions × ``n_envs``.

    States thread through the scan (each step consumes the previous step's
    delivered state), so the three programs do real sequential work; targets
    are fixed.  Interleaved rounds, min per variant.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import ChargaxEnv, EnvConfig, transition
    from repro.kernels.chargax_step import ops
    from repro.utils import replace

    env = ChargaxEnv(EnvConfig())
    params = env.default_params
    fp = replace(params, pole=ops.build_pole_params(params))
    dt = env.config.dt_hours
    n = env.n_evse

    keys = jax.random.split(jax.random.key(0), n_envs)
    _, state = jax.vmap(env.reset)(keys)
    k1, k2 = jax.random.split(jax.random.key(1))
    te = jax.random.uniform(k1, (n_envs, n), minval=-1.0, maxval=1.0) * params.evse_max_current
    tb = jax.random.uniform(k2, (n_envs,), minval=-1.0, maxval=1.0) * params.batt_max_current

    def staged_one(s, e, b):
        applied = transition.request(params, s, e, b, dt)
        alloc = transition.allocate(params, s, applied)
        return alloc, transition.deliver(params, s, alloc.applied, dt)

    def fused_one(impl):
        return lambda s, e, b: ops.fused_transition(fp, s, e, b, dt, impl=impl)

    def chained(one):
        v = jax.vmap(one)

        @jax.jit
        def run_chain(state, te, tb):
            def body(s, _):
                alloc, charged = v(s, te, tb)
                return charged.state, alloc.power_kw.sum()
            s, p = jax.lax.scan(body, state, None, n_iters)
            return s, p.sum()

        return run_chain

    fns = {
        "staged": chained(staged_one),
        "fused_ref": chained(fused_one("ref")),
        "pallas_interpret": chained(fused_one("interpret")),
    }
    for fn in fns.values():  # compile everything before timing
        _, p = fn(state, te, tb)
        jax.block_until_ready(p)

    best = {k: float("inf") for k in fns}
    for _ in range(max(rounds, 1)):
        for k, fn in fns.items():  # interleaved
            t0 = time.perf_counter()
            _, p = fn(state, te, tb)
            jax.block_until_ready(p)
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


LAST_SUMMARY: dict | None = None  # set by run(); persisted by benchmarks.run


def run(quick: bool = True):
    global LAST_SUMMARY
    import jax

    dr = dryrun_table()
    rf = roofline_table()
    n = dr.count("| Y |")
    rows = [("dryrun_cells_ok", float(n), "see results/dryrun.json")]

    n_envs, n_iters = (128, 20) if quick else (512, 50)
    t = bench_kernel_vs_reference(n_envs, n_iters, rounds=3)
    per_step = {k: v / (n_iters * n_envs) * 1e6 for k, v in t.items()}
    frac = t["fused_ref"] / t["staged"] - 1.0
    rows.append(("kernel_staged", per_step["staged"], f"{n_envs} envs x {n_iters} chained"))
    rows.append(
        ("kernel_fused_ref", per_step["fused_ref"], f"fused-ref-vs-staged {frac:+.2%}")
    )
    rows.append(
        (
            "kernel_pallas_interpret",
            per_step["pallas_interpret"],
            "interpret-mode emulation cost (compiled kernel needs a TPU)",
        )
    )
    LAST_SUMMARY = {
        "kernel_n_envs": n_envs,
        "kernel_n_iters": n_iters,
        "staged_us_per_env_step": round(per_step["staged"], 3),
        "fused_ref_us_per_env_step": round(per_step["fused_ref"], 3),
        "pallas_interpret_us_per_env_step": round(per_step["pallas_interpret"], 3),
        "fused_ref_vs_staged_frac": round(frac, 4),
        "backend": jax.default_backend(),
    }
    return rows


if __name__ == "__main__":
    print("## Dry-run\n")
    print(dryrun_table())
    print("\n## Roofline\n")
    print(roofline_table())
    print("\n## Kernel vs reference\n")
    for name, us, derived in run()[1:]:
        print(f"{name},{us:.2f},{derived}")
