"""Roofline analysis from the dry-run's compiled artifacts.

For each (arch x shape x mesh) cell:
    compute term    = per-device HLO FLOPs / 197 TFLOP/s (bf16, v5e-class)
    memory term     = per-device HBM-traffic proxy / 819 GB/s
    collective term = per-device collective bytes / 50 GB/s per-link ICI
(the HLO is post-SPMD, so parser totals are already per device).

Also reports MODEL_FLOPS = 6*N*D (train) / 2*N_active*D (inference) and
the usefulness ratio MODEL_FLOPS / HLO_FLOPs, which exposes remat
recompute and dispatch overhead.

Usage:
    PYTHONPATH=src python -m repro.perf.roofline \
        --dryrun-dir experiments/dryrun --out experiments/roofline.json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from functools import partial
from typing import Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link


def model_flops_per_device(arch: str, shape_name: str, n_devices: int,
                           microbatches: int = 1) -> float:
    """6*N*tokens for train, 2*N_active*tokens for inference (global),
    divided by device count.  N counted via eval_shape (no allocation)."""
    import jax
    import numpy as np
    from repro.configs import get_config, SHAPES_BY_NAME
    from repro.launch.specs import params_struct

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    pshape = params_struct(cfg)
    leaves = jax.tree_util.tree_leaves_with_path(pshape)
    total = 0
    expert_params = 0
    embed_params = 0
    for path, leaf in leaves:
        names = [str(getattr(k, "key", k)) for k in path]
        total += leaf.size
        if "moe" in names and names[-1] in ("w1", "w2", "w3"):
            expert_params += leaf.size
        if names[-1] in ("embed", "unembed"):
            embed_params += leaf.size
    m = cfg.moe
    n_active = total - embed_params
    if m.n_experts:
        n_active -= expert_params * (m.n_experts - m.top_k) / m.n_experts
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        flops = 2.0 * n_active * shape.global_batch
    return flops / n_devices


def roofline_terms(per_dev_flops: float, per_dev_bytes: float,
                   per_dev_coll_bytes: float) -> dict:
    terms = {
        "compute_s": per_dev_flops / PEAK_FLOPS,
        "memory_s": per_dev_bytes / HBM_BW,
        "collective_s": per_dev_coll_bytes / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    total = max(terms.values())
    terms["dominant"] = dominant
    # roofline fraction: useful-compute time over the bound-resource time
    terms["step_lower_bound_s"] = total
    return terms


def layer_trips_for(arch: str) -> set:
    """Known layer-scan trip counts for an arch (used to tell layer scans
    apart from kernel-interior scans in the kernelized memory term)."""
    from repro.configs import get_config
    cfg = get_config(arch)
    trips = {cfg.n_layers}
    if cfg.family == "vlm":
        g = cfg.cross_attn_every - 1
        trips = {cfg.n_layers // cfg.cross_attn_every, g}
    elif cfg.family == "hybrid":
        from repro.models.hybrid import scan_trips
        trips = scan_trips(cfg)
    elif cfg.family == "encdec":
        trips = {cfg.n_layers, cfg.n_encoder_layers}
    return trips


def analyze_cell(json_path: str) -> Optional[dict]:
    with open(json_path) as f:
        rec = json.load(f)
    if not rec.get("ok") or "hlo" not in rec:
        return None
    from repro.perf.hlo_analysis import analyze_hlo_file
    hlo_path = rec["hlo"]
    if not os.path.exists(hlo_path):
        hlo_path = os.path.join(os.path.dirname(json_path),
                                os.path.basename(hlo_path))
    parsed = analyze_hlo_file(hlo_path,
                              layer_trips=layer_trips_for(rec["arch"]))
    n_dev = rec["n_devices"]
    mflops = model_flops_per_device(rec["arch"], rec["shape"], n_dev,
                                    rec.get("microbatches", 1))
    terms = roofline_terms(parsed["flops"],
                           parsed.get("bytes_kernelized", parsed["bytes"]),
                           parsed["collective_bytes"])
    terms["memory_xla_s"] = parsed["bytes"] / HBM_BW
    mfu_at_bound = (mflops / PEAK_FLOPS) / max(terms["step_lower_bound_s"],
                                               1e-30)
    out = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "n_devices": n_dev,
        "microbatches": rec.get("microbatches", 1),
        "per_device": {
            "hlo_flops": parsed["flops"],
            "hbm_bytes": parsed.get("bytes_kernelized", parsed["bytes"]),
            "hbm_bytes_xla": parsed["bytes"],
            "collective_bytes": parsed["collective_bytes"],
            "collectives_by_type": parsed["collectives_by_type"],
            "model_flops": mflops,
            "memory_gib": rec["memory"]["per_device_total"] / 2**30,
        },
        "terms": terms,
        "useful_flops_ratio": mflops / max(parsed["flops"], 1.0),
        "roofline_fraction": min(mfu_at_bound, 1.0),
        "unknown_trip_whiles": parsed["unknown_trip_whiles"],
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline.json")
    ap.add_argument("--mesh", default="single",
                    help="mesh to tabulate (single|multi|both)")
    args = ap.parse_args()

    rows = []
    for path in sorted(glob.glob(os.path.join(args.dryrun_dir, "*.json"))):
        if args.mesh != "both" and not path.endswith(f"_{args.mesh}.json"):
            continue
        row = analyze_cell(path)
        if row:
            rows.append(row)
            t = row["terms"]
            print(f"{row['arch']:22s} {row['shape']:12s} {row['mesh']:6s} "
                  f"C={t['compute_s']:9.2e} M={t['memory_s']:9.2e} "
                  f"N={t['collective_s']:9.2e} dom={t['dominant'][:-2]:10s} "
                  f"useful={row['useful_flops_ratio']:6.2f} "
                  f"roofline={row['roofline_fraction']:6.1%}", flush=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {len(rows)} rows -> {args.out}")


if __name__ == "__main__":
    main()
