import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). 512 placeholder host devices back the production
# meshes: 16x16 single pod and 2x16x16 multi-pod.
"""Multi-pod dry-run: .lower().compile() every (architecture × input-shape ×
mesh) cell, print memory/cost analysis, and dump roofline inputs as JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 512-chip pass
  PYTHONPATH=src python -m repro.launch.dryrun --list

Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..configs.shapes import SHAPES, applicable, input_specs
from ..launch.compile_cache import use_compile_cache
from ..launch.hlo_analysis import collective_bytes, cost_stats, memory_stats
from ..launch.jaxpr_cost import loop_trip_table, traced_cost
from ..launch.mesh import make_production_mesh
from ..models import Model
from ..models.common import dp_axes, param_template, unflatten
from ..models.lm import _hybrid_plan
from ..optim import OptConfig, opt_state_specs
from ..runtime.train_loop import make_train_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# grad-accumulation dtype: bf16 for >=10B params so the accumulation buffer
# fits the 16 GB/chip budget alongside fp32 optimizer state (see DESIGN.md)
BF16_ACCUM_THRESHOLD = 10e9


def abstract_opt_state(cfg, mesh, parallelism: str = "tp"):
    """ShapeDtypeStructs for the AdamW state with ZeRO-1 shardings."""
    from ..models.common import resolved_spec
    from ..optim import zero_spec
    defs = param_template(cfg)
    zspecs = {path: zero_spec(d.shape, resolved_spec(d, mesh, parallelism),
                              mesh.shape["data"])
              for path, d in defs.items()}

    def tree():
        return unflatten({
            path: jax.ShapeDtypeStruct(
                d.shape, jnp.float32,
                sharding=NamedSharding(mesh, zspecs[path]))
            for path, d in defs.items()})

    count = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    return {"m": tree(), "v": tree(), "master": tree(), "count": count}


def _layers_per_scan(cfg) -> float:
    """Average trips of one layer-scan body (hybrid splits the stack into
    full/SWA segment scans)."""
    if cfg.family == "hybrid":
        plan = _hybrid_plan(cfg)
        return cfg.num_layers / max(1, len(plan))
    return float(cfg.num_layers)


def lower_cell(arch: str, shape_name: str, mesh, *,
               num_microbatches: int | None = None,
               parallelism: str = "tp", kv_quant: bool = False,
               moe_chunked: bool = False):
    """Returns (lowered, jaxpr_cost_fn, n_devices, meta) for one cell."""
    import dataclasses
    cfg = configs.get(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    if moe_chunked:
        cfg = dataclasses.replace(cfg, moe_chunk_dispatch=True)
    shape = SHAPES[shape_name]
    model = Model(cfg, mesh, parallelism=parallelism)
    inputs = input_specs(cfg, shape, mesh, parallelism=parallelism)
    dp_total = 1
    for a in dp_axes(mesh):
        dp_total *= mesh.shape[a]
    if parallelism == "fsdp":
        dp_total *= mesh.shape["model"]
        if shape.kind == "train" and shape.global_batch % dp_total != 0:
            dp_total //= mesh.shape["model"]   # hybrid FSDP: batch on data only

    if shape.kind == "train":
        if num_microbatches is None:
            num_microbatches = max(1, shape.global_batch // dp_total)
        accum = (jnp.bfloat16 if cfg.param_count() >= BF16_ACCUM_THRESHOLD
                 else jnp.float32)
        step = make_train_step(model, OptConfig(),
                               num_microbatches=num_microbatches,
                               accum_dtype=accum, donate=True)
        params = model.abstract_params()
        opt = abstract_opt_state(cfg, mesh, parallelism)
        step_idx = jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))
        with jax.set_mesh(mesh):
            lowered = step.lower(params, opt, inputs, step_idx)
        cost_fn = lambda: traced_cost(step, params, opt, inputs, step_idx)
        meta = {"kind": "train", "num_microbatches": num_microbatches,
                "accum_dtype": str(np.dtype("bfloat16") if accum == jnp.bfloat16
                                   else np.dtype("float32"))}
        trip_table = loop_trip_table(
            "train", num_layers=_layers_per_scan(cfg),
            num_microbatches=num_microbatches,
            kv_blocks=max(1, shape.seq_len // (cfg.ssm_chunk or 512))
            if cfg.family in ("ssm", "hybrid") else 1)
    elif shape.kind == "prefill":
        params = model.abstract_params()
        fn = jax.jit(lambda p, b: model.prefill(p, b))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params, inputs)
        cost_fn = lambda: traced_cost(fn, params, inputs)
        meta = {"kind": "prefill"}
        kvb = max(shape.seq_len // 512,
                  (shape.seq_len // cfg.ssm_chunk)
                  if cfg.family in ("ssm", "hybrid") else 1)
        trip_table = loop_trip_table("prefill",
                                     num_layers=_layers_per_scan(cfg),
                                     kv_blocks=kvb)
    else:  # decode
        params = model.abstract_params()
        cache = model.abstract_cache(shape.global_batch, shape.seq_len)
        fn = jax.jit(model.decode_step, donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = fn.lower(params, cache, inputs["tokens"])
        cost_fn = lambda: traced_cost(fn, params, cache, inputs["tokens"])
        meta = {"kind": "decode"}
        trip_table = loop_trip_table("decode",
                                     num_layers=_layers_per_scan(cfg))
    meta["trip_table"] = {str(k): v for k, v in trip_table.items()}
    return lowered, cost_fn, trip_table, mesh.devices.size, meta, cfg, shape


def run_cell(arch: str, shape_name: str, mesh_name: str, mesh,
             out_dir: Path, parallelism: str = "tp",
             kv_quant: bool = False, moe_chunked: bool = False) -> dict:
    t0 = time.monotonic()
    lowered, cost_fn, trip_table, n_dev, meta, cfg, shape = lower_cell(
        arch, shape_name, mesh, parallelism=parallelism, kv_quant=kv_quant,
        moe_chunked=moe_chunked)
    meta["parallelism"] = parallelism
    meta["kv_quant"] = kv_quant
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0

    mem = memory_stats(compiled)
    cost = cost_stats(compiled)                 # raw XLA (loops counted once)
    jcost = cost_fn().as_dict()                 # exact trip-count-aware, GLOBAL
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo, n_dev, trip_table)

    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_devices": n_dev, "meta": meta,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
        "lower_sec": round(t_lower, 2), "compile_sec": round(t_compile, 2),
        "memory": mem,
        "cost_xla_raw": cost,                   # documented undercount
        "cost_traced_global": jcost,            # divide by n_devices per chip
        "collectives": {k: v for k, v in coll.items() if k != "examples"},
        "collective_examples": coll["examples"][:12],
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{arch}__{shape_name}.json").write_text(json.dumps(art, indent=1))

    print(f"[{mesh_name}] {arch} × {shape_name}: compile {t_compile:.1f}s | "
          f"per-chip flops {jcost['flops']/n_dev:.3e} | "
          f"hbm {mem.get('total_hbm_bytes', 0)/2**30:.2f} GiB | "
          f"collective {coll['total_bytes']/2**20:.1f} MiB/chip")
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis(raw xla): {cost}")
    return art


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--parallelism", choices=("tp", "fsdp"), default="tp")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode cells")
    ap.add_argument("--moe-chunked", action="store_true",
                    help="all-to-all MoE dispatch (per-data-shard capacity)")
    ap.add_argument("--suffix", default="",
                    help="artifact directory suffix (e.g. -fsdp)")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()

    use_compile_cache()

    cells = []
    for arch in (configs.ARCHS if args.arch is None else [args.arch]):
        cfg = configs.get(arch)
        for shape_name in (SHAPES if args.shape is None else [args.shape]):
            ok, why = applicable(cfg, shape_name)
            cells.append((arch, shape_name, ok, why))
    if args.list:
        for c in cells:
            print(c)
        return 0

    meshes = {"single": make_production_mesh(multi_pod=False),
              "multi": make_production_mesh(multi_pod=True)}
    if args.mesh != "both":
        meshes = {args.mesh: meshes[args.mesh]}

    failures, skipped, passed = [], [], []
    for mesh_name, mesh in meshes.items():
        out_dir = Path(args.out) / (mesh_name + args.suffix)
        for arch, shape_name, ok, why in cells:
            if not ok:
                skipped.append((mesh_name, arch, shape_name, why))
                print(f"[{mesh_name}] {arch} × {shape_name}: SKIP ({why})")
                # record the skip as an artifact for the roofline table
                out_dir.mkdir(parents=True, exist_ok=True)
                (out_dir / f"{arch}__{shape_name}.json").write_text(
                    json.dumps({"arch": arch, "shape": shape_name,
                                "mesh": mesh_name, "skipped": why}))
                continue
            try:
                run_cell(arch, shape_name, mesh_name, mesh, out_dir,
                         parallelism=args.parallelism,
                         kv_quant=args.kv_quant,
                         moe_chunked=args.moe_chunked)
                passed.append((mesh_name, arch, shape_name))
            except Exception as e:   # noqa: BLE001 — report, keep going
                traceback.print_exc()
                failures.append((mesh_name, arch, shape_name, repr(e)[:200]))

    print(f"\n=== dry-run summary: {len(passed)} passed, "
          f"{len(skipped)} skipped (documented), {len(failures)} FAILED ===")
    for f in failures:
        print("FAILED:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
