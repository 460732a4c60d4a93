import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

_DOC = """Multi-pod dry-run: AOT-compile every (arch x shape x mesh) cell.

For each cell this lowers the real train/prefill/serve step with
ShapeDtypeStruct stand-ins (no allocation), compiles it for the production
mesh built from 512 forced host devices, and records:

  * compiled.memory_analysis()  — proves the cell fits per-device HBM,
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline,
  * collective bytes parsed from the optimized HLO (§Roofline third term).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_stats
from repro.configs.registry import ARCH_IDS, applicable_shapes, build_model, get_config
from repro.distributed import sharding as shd
from repro.distributed.train_step import (
    TrainState,
    TrainStepConfig,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES, ModelConfig, ShapeConfig
from repro.optim import AdamWState


def default_microbatches(cfg: ModelConfig, shape: ShapeConfig, n_devices: int) -> int:
    """Grad-accumulation so per-microbatch activations fit HBM comfortably.

    With chunked CE (§Perf iteration 1) the logits no longer dominate; the
    bound is per-layer activation residuals: target <= 128k tokens per
    microbatch at d_model ~ 2-4k, scaled down for the 8k-wide archs.
    """
    if shape.kind != "train":
        return 1
    token_budget = max(int(131_072 * 4096 / max(cfg.d_model, 1024)), 16_384)
    mb = 1
    while shape.tokens / mb > token_budget and mb < shape.global_batch:
        mb *= 2
    while shape.global_batch % mb != 0:
        mb *= 2
    return min(mb, shape.global_batch)


def model_inputs(cfg: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, l = shape.global_batch, shape.seq_len
    tok_sharding = NamedSharding(mesh, P(*shd.batch_spec(mesh, b), None))
    sds = lambda s, d, sh: jax.ShapeDtypeStruct(s, d, sharding=sh)
    batch = {
        "tokens": sds((b, l), jnp.int32, tok_sharding),
        "labels": sds((b, l), jnp.int32, tok_sharding),
    }
    if cfg.family == "encdec":
        frame_sharding = NamedSharding(mesh, P(*shd.batch_spec(mesh, b), None, "model"))
        batch["frames"] = sds((b, cfg.enc_seq, cfg.d_model), jnp.float32, frame_sharding)
    return batch


def input_specs(arch: str, shape_name: str = "train_4k", multi_pod: bool = False):
    """Public helper (assignment step 2): stand-ins for every model input."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    return model_inputs(cfg, SHAPES[shape_name], mesh)


def _abstract(fn, *args):
    return jax.eval_shape(fn, *args)


def lower_cell(arch: str, shape_name: str, multi_pod: bool, smoke: bool = False, strategy: str = "2d", microbatches: int | None = None) -> dict:
    """Lower + compile one cell; returns the §Dry-run/§Roofline record."""
    shd.set_strategy(strategy)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch, smoke=smoke)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    n_dev = mesh.devices.size
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
    }

    key = jax.random.key(0)
    params_abs = _abstract(model.init, key)
    params_sh = shd.param_shardings(params_abs, mesh)
    rep = NamedSharding(mesh, P())

    t0 = time.perf_counter()
    ctx = jax.sharding.set_mesh(mesh)  # ambient mesh for activation constraints
    ctx.__enter__()
    if shape.kind == "train":
        mb = microbatches or default_microbatches(cfg, shape, n_dev)
        record["num_microbatches"] = mb
        ts_cfg = TrainStepConfig(num_microbatches=mb)
        step = make_train_step(model, ts_cfg)

        opt_abs = _abstract(lambda p: AdamWState(
            step=jnp.int32(0),
            mu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
            nu=jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
        ), params_abs)
        state_abs = TrainState(params=params_abs, opt=opt_abs, error_feedback={})
        state_sh = TrainState(
            params=params_sh,
            opt=AdamWState(step=rep, mu=params_sh, nu=params_sh),
            error_feedback={},
        )
        batch = model_inputs(cfg, shape, mesh)
        batch_sh = jax.tree_util.tree_map(lambda s: s.sharding, batch)
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_abs, batch)
    elif shape.kind == "prefill":
        step = make_prefill_step(model)
        batch = model_inputs(cfg, shape, mesh)
        jitted = jax.jit(step, in_shardings=(params_sh, jax.tree_util.tree_map(lambda s: s.sharding, batch)))
        lowered = jitted.lower(params_abs, batch)
    else:  # decode
        b, l = shape.global_batch, shape.seq_len
        step = make_serve_step(model)
        if cfg.family == "encdec":
            enc_abs = jax.ShapeDtypeStruct((b, cfg.enc_seq, cfg.d_model), jnp.float32)
            cache_abs = _abstract(
                lambda p, e: model.init_cache(p, b, l, e), params_abs, enc_abs
            )
        else:
            cache_abs = _abstract(lambda: model.init_cache(b, l))
        cache_sh = shd.cache_shardings(cache_abs, mesh, b)
        tok_sh = NamedSharding(mesh, P(*shd.batch_spec(mesh, b), None))
        tok_abs = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(
            step,
            in_shardings=(params_sh, cache_sh, tok_sh, rep),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_abs, cache_abs, tok_abs, pos_abs)

    record["lower_s"] = round(time.perf_counter() - t0, 2)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    ctx.__exit__(None, None, None)
    record["compile_s"] = round(time.perf_counter() - t1, 2)

    # --- memory analysis (proves it fits) -----------------------------------
    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            v = getattr(mem, attr, None)
            if v is not None:
                record[attr] = int(v)
        # memory_analysis sizes are per-device (SPMD program) — verified
        # against the sharded KV-cache size of the decode cells
        args_b = record.get("argument_size_in_bytes", 0)
        temp_b = record.get("temp_size_in_bytes", 0)
        record["bytes_per_device"] = int(args_b + temp_b)
        record["fits_16g_hbm"] = bool(args_b + temp_b <= 16 * 2**30)

    # --- cost analysis (FLOPs / bytes for §Roofline) -------------------------
    cost = compiled.cost_analysis() or {}
    if cost:
        record["hlo_flops"] = float(cost.get("flops", -1))
        record["hlo_bytes"] = float(cost.get("bytes accessed", -1))

    # --- collective bytes from the optimized HLO -----------------------------
    record["collectives"] = collective_stats(compiled.as_text())
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strategy", default="2d", choices=["2d", "fsdp", "dp"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        shapes = (
            [s.name for s in applicable_shapes(arch)]
            if (args.all or args.shape is None)
            else [args.shape]
        )
        for shape in shapes:
            meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]
            for mp in meshes:
                cells.append((arch, shape, mp))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if r.get("ok")}

    for arch, shape, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        if (arch, shape, mesh_name) in done:
            print(f"[skip] {arch} {shape} {mesh_name} (cached)")
            continue
        print(f"[cell] {arch} {shape} {mesh_name} ...", flush=True)
        t0 = time.perf_counter()
        try:
            rec = lower_cell(arch, shape, mp, smoke=args.smoke, strategy=args.strategy, microbatches=args.microbatches)
            rec["ok"] = True
            print(
                f"   ok: compile {rec['compile_s']}s, "
                f"{rec.get('bytes_per_device', 0)/2**30:.2f} GiB/dev, "
                f"{rec.get('hlo_flops', 0):.3e} flops",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — record and continue
            rec = {
                "arch": arch,
                "shape": shape,
                "mesh": mesh_name,
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
            print(f"   FAIL: {rec['error'][:200]}", flush=True)
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        results = [
            r for r in results
            if not (r["arch"] == arch and r["shape"] == shape and r["mesh"] == mesh_name)
        ] + [rec]
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells ok -> {args.out}")


if __name__ == "__main__":
    main()
