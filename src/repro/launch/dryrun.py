import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell against the production meshes and record memory/cost/collective
evidence for the roofline analysis.

The two lines above MUST stay the first statements in this module: jax
locks the device count on first init, and the dry-run needs 512 host
placeholder devices.  (Only the dry-run — smoke tests and benches see 1.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --out experiments/dryrun
"""
import argparse
import dataclasses
import gzip
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs, shapes_for, SHAPES_BY_NAME
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as lspecs
from repro.models import decode_step, prefill
from repro.models.moe import resolve_groups
from repro.train.optimizer import for_model, opt_state_specs
from repro.train.train_step import (
    ACT_BUDGET, HBM_BYTES, make_train_step, resolve_microbatches)


def _resolve_moe(cfg: ModelConfig, shape: ShapeConfig, mesh) -> ModelConfig:
    if cfg.moe.n_experts == 0:
        return cfg
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    g = resolve_groups(cfg, tokens, shd.axis_size(mesh, shd.batch_axes(mesh)))
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_groups=g))


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               microbatches=None):
    """Returns (jitted_fn, abstract_args, extras) for one dry-run cell."""
    cfg = _resolve_moe(cfg, shape, mesh)
    pshape = lspecs.params_struct(cfg)
    pspecs = shd.param_specs(cfg, pshape, mesh)
    pshard = shd.to_shardings(mesh, pspecs)

    if shape.kind == "train":
        ocfg = for_model(cfg)
        oshape = lspecs.opt_state_struct(ocfg, pshape)
        ospecs = opt_state_specs(ocfg, pspecs, pshape)
        oshard = shd.to_shardings(mesh, ospecs)
        bshard = shd.to_shardings(mesh, shd.batch_specs(cfg, shape, mesh))
        k = microbatches or resolve_microbatches(
            cfg, shape.global_batch, shape.seq_len,
            shd.axis_size(mesh, shd.batch_axes(mesh)),
            budget_bytes=int(HBM_BYTES * ACT_BUDGET))
        step = make_train_step(cfg, ocfg, microbatches=k)
        jitted = jax.jit(step,
                         in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))
        args = (pshape, oshape, lspecs.batch_specs_struct(cfg, shape))
        return jitted, args, {"microbatches": k}

    if shape.kind == "prefill":
        bshard = shd.to_shardings(mesh, shd.batch_specs(cfg, shape, mesh))
        cshard = shd.to_shardings(mesh, shd.cache_specs(cfg, shape, mesh))

        def prefill_step(params, batch):
            return prefill(params, cfg, batch)

        jitted = jax.jit(prefill_step,
                         in_shardings=(pshard, bshard),
                         out_shardings=(cshard, None, None))
        args = (pshape, lspecs.batch_specs_struct(cfg, shape))
        return jitted, args, {}

    # decode: one new token against a seq_len cache
    cshard = shd.to_shardings(mesh, shd.cache_specs(cfg, shape, mesh))
    ba = shd.batch_axes(mesh)
    b = shd._fit(mesh, shape.global_batch, ba)
    tshard = NamedSharding(mesh, P(b, None))
    posshard = NamedSharding(mesh, P(b))

    def serve_step(params, cache, tokens, pos):
        return decode_step(params, cfg, cache, tokens, pos)

    jitted = jax.jit(serve_step,
                     in_shardings=(pshard, cshard, tshard, posshard),
                     out_shardings=(None, cshard),
                     donate_argnums=(1,))
    din = lspecs.decode_inputs_struct(cfg, shape)
    args = (pshape, lspecs.cache_struct(cfg, shape), din["tokens"],
            din["pos"])
    return jitted, args, {}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = True) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False}
    t0 = time.time()
    try:
        cfg = get_config(arch)
        shape = SHAPES_BY_NAME[shape_name]
        mesh = make_production_mesh(multi_pod=multi_pod)
        shd.set_activation_axes(shd.batch_axes(mesh), mesh=mesh)
        jitted, args, extra = build_cell(cfg, shape, mesh)
        rec.update(extra)
        try:
            with mesh:
                lowered = jitted.lower(*args)
                t_lower = time.time()
                compiled = lowered.compile()
                t_compile = time.time()
        finally:
            shd.set_activation_axes(None)
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        rec.update({
            "ok": True,
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "n_devices": mesh.devices.size,
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
            },
            "cost": {"flops": ca.get("flops", 0.0),
                     "bytes_accessed": ca.get("bytes accessed", 0.0)},
        })
        # per-device peak proxy: args + temps (aliased args are donated)
        rec["memory"]["per_device_total"] = (
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        if save_hlo:
            hlo_path = os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_name}.hlo.txt.gz")
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
            rec["hlo"] = hlo_path
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"{arch}_{shape_name}_{mesh_name}.json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def iter_cells(archs, shapes_filter, meshes):
    for arch in archs:
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            if shapes_filter and shape.name not in shapes_filter:
                continue
            for mesh in meshes:
                yield arch, shape.name, mesh == "multi"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = args.arch or list_archs()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = list(iter_cells(archs, args.shape, meshes))
    if args.list:
        for c in cells:
            print(*c)
        return

    failures = 0
    for i, (arch, shape, multi) in enumerate(cells):
        rec = run_cell(arch, shape, multi, args.out,
                       save_hlo=not args.no_hlo)
        status = "OK " if rec["ok"] else "FAIL"
        mem = rec.get("memory", {}).get("per_device_total", 0) / 2**30
        print(f"[{i + 1}/{len(cells)}] {status} {arch} {shape} "
              f"{'multi' if multi else 'single'} "
              f"mem/dev={mem:.2f}GiB t={rec['total_s']}s"
              + ("" if rec["ok"] else f"  {rec.get('error', '')[:200]}"),
              flush=True)
        failures += 0 if rec["ok"] else 1
    print(f"done: {len(cells) - failures}/{len(cells)} cells OK")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
