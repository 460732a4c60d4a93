"""Three-term roofline analysis from AOT-compiled artifacts (assignment §Roofline).

Hardware model (TPU v5e): 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI.

XLA counts ``while`` bodies ONCE in ``cost_analysis()`` (verified in
tests/launch), so scanned-layer cells under-report.  We therefore derive the
roofline terms from **unrolled probe compiles on the production mesh**:
reduced-layer-count configs with full layer dimensions, ``unroll=True`` (no
while loops -> exact per-device FLOPs/bytes/collective counts), solved
linearly for (fixed, per-layer[, per-shared-block]) marginals and
extrapolated to the full depth.  num_microbatches=1 in probes; train totals
scale by the cell's microbatch count (identical per-microbatch work).

Outputs per (arch × shape × mesh): the three terms in seconds, the dominant
bottleneck, MODEL_FLOPS = 6·N_active·D, and the useful-compute ratio.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_stats, materialized_bytes
from repro.configs.registry import build_model, get_config
from repro.models.config import SHAPES, ModelConfig, ShapeConfig

# ---------------------------------------------------------------------------
# hardware constants (TPU v5e)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link


# ---------------------------------------------------------------------------
# probe configs per family: (cfg_variant, coefficient row); unknowns x solve
# A x = b per metric, full total = c . x
# ---------------------------------------------------------------------------
def probe_plan(cfg: ModelConfig) -> tuple[list[tuple[ModelConfig, list[float]]], list[float]]:
    r = dataclasses.replace
    if cfg.family == "encdec":
        probes = [
            (r(cfg, n_layers=1, n_enc_layers=1), [1, 1]),
            (r(cfg, n_layers=2, n_enc_layers=2), [1, 2]),
        ]
        full = [1, cfg.n_layers]
    elif cfg.alt_local_global:
        probes = [(r(cfg, n_layers=2), [1, 1]), (r(cfg, n_layers=4), [1, 2])]
        full = [1, cfg.n_layers // 2]
    elif cfg.family == "hybrid":
        probes = [
            (r(cfg, n_layers=1, shared_attn_every=1), [1, 1, 1]),
            (r(cfg, n_layers=2, shared_attn_every=1), [1, 2, 2]),
            (r(cfg, n_layers=2, shared_attn_every=2), [1, 2, 1]),
        ]
        k = cfg.shared_attn_every
        n_groups = (cfg.n_layers + k - 1) // k
        full = [1, cfg.n_layers, n_groups]
    else:
        probes = [(r(cfg, n_layers=1), [1, 1]), (r(cfg, n_layers=2), [1, 2])]
        full = [1, cfg.n_layers]
    return probes, full


def _compile_probe(cfg: ModelConfig, shape: ShapeConfig, mesh, microbatches: int, remat: bool = True) -> dict:
    """Compile one probe (unrolled, mb=1, microbatch-sized batch) -> metrics."""
    from repro.distributed import sharding as shd
    from repro.distributed.train_step import (
        TrainState,
        TrainStepConfig,
        make_prefill_step,
        make_serve_step,
        make_train_step,
    )
    from repro.launch import dryrun
    from repro.optim import AdamWState

    model = build_model(cfg)
    rep = NamedSharding(mesh, P())
    ctx = jax.sharding.set_mesh(mesh)
    ctx.__enter__()
    key = jax.random.key(0)
    params_abs = jax.eval_shape(model.init, key)
    params_sh = shd.param_shardings(params_abs, mesh)

    if shape.kind == "train":
        micro_shape = dataclasses.replace(
            shape, global_batch=max(shape.global_batch // microbatches, 1)
        )
        batch = dryrun.model_inputs(cfg, micro_shape, mesh)
        ts_cfg = TrainStepConfig(num_microbatches=1, unroll_layers=True, remat=remat)
        step = make_train_step(model, ts_cfg)
        opt_abs = jax.eval_shape(
            lambda p: AdamWState(
                step=jnp.int32(0),
                mu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
                nu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
            ),
            params_abs,
        )
        state_abs = TrainState(params=params_abs, opt=opt_abs, error_feedback={})
        state_sh = TrainState(
            params=params_sh,
            opt=AdamWState(step=rep, mu=params_sh, nu=params_sh),
            error_feedback={},
        )
        batch_sh = jax.tree_util.tree_map(lambda s: s.sharding, batch)
        compiled = (
            jax.jit(step, in_shardings=(state_sh, batch_sh), donate_argnums=(0,))
            .lower(state_abs, batch)
            .compile()
        )
    elif shape.kind == "prefill":
        batch = dryrun.model_inputs(cfg, shape, mesh)
        step = make_prefill_step(model, unroll=True)
        compiled = (
            jax.jit(
                step,
                in_shardings=(params_sh, jax.tree_util.tree_map(lambda s: s.sharding, batch)),
            )
            .lower(params_abs, batch)
            .compile()
        )
    else:
        from repro.distributed.sharding import batch_spec, cache_shardings

        b, l = shape.global_batch, shape.seq_len
        step = make_serve_step(model, unroll=True)
        if cfg.family == "encdec":
            enc_abs = jax.ShapeDtypeStruct((b, cfg.enc_seq, cfg.d_model), jnp.float32)
            cache_abs = jax.eval_shape(
                lambda p, e: model.init_cache(p, b, l, e), params_abs, enc_abs
            )
        else:
            cache_abs = jax.eval_shape(lambda: model.init_cache(b, l))
        cache_sh = cache_shardings(cache_abs, mesh, b)
        tok_sh = NamedSharding(mesh, P(*batch_spec(mesh, b), None))
        compiled = (
            jax.jit(
                step,
                in_shardings=(params_sh, cache_sh, tok_sh, rep),
                donate_argnums=(1,),
            )
            .lower(
                params_abs,
                cache_abs,
                jax.ShapeDtypeStruct((b, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            )
            .compile()
        )

    ctx.__exit__(None, None, None)
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll = collective_stats(text)
    mem = compiled.memory_analysis()
    args_bytes = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        # fusion model: materialization points + one read of the program args
        "bytes_fused": float(materialized_bytes(text)) + args_bytes,
        "collective_bytes": float(coll["total_bytes"]),
        "collective_count": int(coll["total_count"]),
    }


def model_params_active(cfg: ModelConfig) -> tuple[float, float]:
    """(total_params, active_params) from abstract shapes; MoE active =
    non-expert + expert * top_k / E."""
    model = build_model(cfg)
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    total = active = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_abs)[0]:
        n = float(np.prod(leaf.shape))
        key = jax.tree_util.keystr(path)
        total += n
        if "expert_w" in key:
            active += n * cfg.top_k / max(cfg.n_experts, 1)
        elif "embed" in key:
            pass  # 6ND convention excludes embedding lookup
        else:
            active += n
    return total, active


def analyze_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    microbatches: int | None = None,
    remat: bool = True,
    cfg_overrides: dict | None = None,
    strategy: str = "2d",
) -> dict:
    """Full §Roofline record for one cell (probe compiles + extrapolation)."""
    from repro.launch.dryrun import default_microbatches
    from repro.launch.mesh import make_production_mesh

    from repro.distributed import sharding as shd

    shd.set_strategy(strategy)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mb = microbatches or default_microbatches(cfg, shape, n_dev)

    probes, full_coeff = probe_plan(cfg)
    rows, results = [], []
    for pcfg, coeff in probes:
        rows.append(coeff)
        results.append(_compile_probe(pcfg, shape, mesh, mb, remat=remat))

    a = np.array(rows, dtype=np.float64)
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "num_microbatches": mb,
        "strategy": strategy,
    }
    scale = mb if shape.kind == "train" else 1
    totals = {}
    for metric in ("flops", "bytes", "bytes_fused", "collective_bytes", "collective_count"):
        b_vec = np.array([r[metric] for r in results])
        x, *_ = np.linalg.lstsq(a, b_vec, rcond=None)
        est = float(np.dot(full_coeff, x))
        if est <= 0 or (x < -1e-6 * max(abs(b_vec).max(), 1)).any():
            # degenerate marginals (decode cells where per-layer deltas are
            # below compile noise): proportional fallback from the largest probe
            i = int(np.argmax(a.sum(axis=1)))
            est = float(b_vec[i]) * (sum(full_coeff) / a[i].sum())
        totals[metric] = est * scale
    record.update({f"per_device_{k}": v for k, v in totals.items()})

    # --- the three roofline terms (seconds, per step) -----------------------
    # memory term uses the TPU-fusion materialisation model; the raw XLA:CPU
    # "bytes accessed" (no fusion — every elementwise operand) is reported
    # alongside as the hard upper bound (EXPERIMENTS.md §Roofline caveat)
    t_compute = totals["flops"] / PEAK_FLOPS
    t_memory = totals["bytes_fused"] / HBM_BW
    t_collective = totals["collective_bytes"] / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    record["t_compute_s"] = t_compute
    record["t_memory_s"] = t_memory
    record["t_memory_raw_s"] = totals["bytes"] / HBM_BW
    record["t_collective_s"] = t_collective
    record["bottleneck"] = max(terms, key=terms.get)
    bound = max(terms.values())
    record["roofline_step_s"] = bound
    record["roofline_fraction_compute"] = t_compute / bound if bound > 0 else 0.0

    # --- model flops & useful-compute ratio ---------------------------------
    total_p, active_p = model_params_active(cfg)
    record["params_total"] = total_p
    record["params_active"] = active_p
    if shape.kind == "train":
        model_flops = 6.0 * active_p * shape.tokens
    elif shape.kind == "prefill":
        model_flops = 2.0 * active_p * shape.tokens
    else:
        model_flops = 2.0 * active_p * shape.global_batch  # one token / seq
    record["model_flops"] = model_flops
    hlo_global = totals["flops"] * n_dev
    record["hlo_flops_global"] = hlo_global
    record["useful_compute_ratio"] = model_flops / hlo_global if hlo_global else 0.0
    # fraction of the roofline spent on USEFUL model flops — the honest score
    # (immune to replicated/wasted compute inflating t_compute)
    t_useful = model_flops / n_dev / PEAK_FLOPS
    record["t_useful_compute_s"] = t_useful
    record["useful_fraction"] = t_useful / bound if bound > 0 else 0.0
    shd.set_strategy("2d")
    return record


def main():
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/roofline.json")
    args = ap.parse_args()

    from repro.configs.registry import ARCH_IDS, applicable_shapes

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        shapes = (
            [s.name for s in applicable_shapes(arch)]
            if (args.all or args.shape is None)
            else [args.shape]
        )
        cells.extend((arch, s) for s in shapes)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"]) for r in results if "bottleneck" in r}

    for arch, shape in cells:
        if (arch, shape) in done:
            print(f"[skip] {arch} {shape}")
            continue
        print(f"[roofline] {arch} {shape} ...", flush=True)
        try:
            rec = analyze_cell(arch, shape)
            print(
                f"   {rec['bottleneck']}-bound: compute {rec['t_compute_s']:.3f}s "
                f"memory {rec['t_memory_s']:.3f}s collective {rec['t_collective_s']:.3f}s "
                f"useful {rec['useful_compute_ratio']:.2f}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            import traceback

            rec = {
                "arch": arch,
                "shape": shape,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-1500:],
            }
            print(f"   FAIL {rec['error'][:150]}", flush=True)
        results = [r for r in results if not (r["arch"] == arch and r["shape"] == shape)]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
