"""Perf hillclimbing (EXPERIMENTS.md §Perf) + the generic 1-D climber.

Two things live here:

  * :class:`HillClimb1D` — a dependency-free discrete hill-climb over a
    ladder of candidate values, with the same improve/back-off
    semantics as ``core.advisor.ThreadAutotuneAdvisor`` (move while the
    new measurement beats the best by >5%, retreat on a >5% regression,
    settle otherwise).  ``repro.io.adaptive`` coordinate-descends two
    of these over chunk size and io depth.
  * the XLA variant-compile driver: compiles named variants of the
    chosen cells on the single-pod mesh and records trip-count-
    corrected roofline terms, so every hypothesis -> change -> before
    -> after row in EXPERIMENTS.md is backed by a JSON artifact.

        PYTHONPATH=src python -m repro.perf.hillclimb --cell llama
        PYTHONPATH=src python -m repro.perf.hillclimb --list

The driver's jax / mesh / config imports (and the
``xla_force_host_platform_device_count`` flag) are confined to the
CLI path so the module itself is import-light — the ingest engine
imports it in-process.
"""
import dataclasses
import json
import os
import time
from typing import Optional, Sequence


class HillClimb1D:
    """Discrete hill-climb over an ordered ladder of candidate values.

    Feed it measurements with ``observe(score)`` (higher is better,
    e.g. bytes/sec); read the value to try next from ``.value``.  The
    climber probes neighbours one step at a time: it keeps moving in a
    direction while each probe improves on the best seen by
    ``improve_ratio``, backs off when a probe regresses past
    ``regress_ratio``, and flips direction / settles otherwise.  Once
    both directions are exhausted it pins the best index until
    ``reset()``.
    """

    def __init__(self, ladder: Sequence, start_index: Optional[int] = None,
                 improve_ratio: float = 1.05, regress_ratio: float = 0.95):
        if not ladder:
            raise ValueError("ladder must be non-empty")
        self.ladder = list(ladder)
        self.improve_ratio = float(improve_ratio)
        self.regress_ratio = float(regress_ratio)
        self._idx = (len(self.ladder) // 2 if start_index is None
                     else max(0, min(int(start_index), len(self.ladder) - 1)))
        self._best_idx = self._idx
        self._best_score: Optional[float] = None
        self._dir = 1 if self._idx < len(self.ladder) - 1 else -1
        self._tried_flip = False
        self._settled = len(self.ladder) == 1
        self.probes = 0

    @property
    def value(self):
        return self.ladder[self._idx]

    @property
    def best(self):
        return self.ladder[self._best_idx]

    @property
    def settled(self) -> bool:
        return self._settled

    def _step_or_settle(self) -> None:
        """Move one rung in the current direction, flipping once; when
        both directions are spent, pin the best index."""
        nxt = self._idx + self._dir
        if 0 <= nxt < len(self.ladder):
            self._idx = nxt
            return
        if not self._tried_flip:
            self._tried_flip = True
            self._dir = -self._dir
            self._idx = self._best_idx
            nxt = self._idx + self._dir
            if 0 <= nxt < len(self.ladder):
                self._idx = nxt
                return
        self._settled = True
        self._idx = self._best_idx

    def observe(self, score: float):
        """Record the measurement for the current ``value``; returns
        the next value to run with."""
        self.probes += 1
        if self._settled:
            return self.value
        if self._best_score is None:
            self._best_score = float(score)
            self._step_or_settle()
            return self.value
        if score > self._best_score * self.improve_ratio:
            self._best_score = float(score)
            self._best_idx = self._idx
            self._step_or_settle()
        elif score < self._best_score * self.regress_ratio:
            # clear regression — retreat to best and try the other way
            if self._tried_flip:
                self._settled = True
                self._idx = self._best_idx
            else:
                self._tried_flip = True
                self._dir = -self._dir
                self._idx = self._best_idx
                self._step_or_settle()
        else:
            # flat: not worth moving further this way
            if score > self._best_score:
                self._best_score = float(score)
                self._best_idx = self._idx
            if self._tried_flip:
                self._settled = True
                self._idx = self._best_idx
            else:
                self._tried_flip = True
                self._dir = -self._dir
                self._idx = self._best_idx
                self._step_or_settle()
        return self.value

    def reset(self, start_index: Optional[int] = None) -> None:
        """Forget history and climb again (workload shifted)."""
        if start_index is not None:
            self._idx = max(0, min(int(start_index), len(self.ladder) - 1))
        else:
            self._idx = self._best_idx
        self._best_idx = self._idx
        self._best_score = None
        self._dir = 1 if self._idx < len(self.ladder) - 1 else -1
        self._tried_flip = False
        self._settled = len(self.ladder) == 1
        self.probes = 0


# --------------------------------------------------------------------------
# XLA variant-compile driver (CLI only from here down)
# --------------------------------------------------------------------------
def _variant(cfg, *, ssm_chunk=None, ssm_intra=None, **cfg_overrides):
    ssm_kw = {}
    if ssm_chunk:
        ssm_kw["chunk_size"] = ssm_chunk
    if ssm_intra:
        ssm_kw["intra_dtype"] = ssm_intra
    if ssm_kw:
        cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm, **ssm_kw))
    return cfg.replace(**cfg_overrides) if cfg_overrides else cfg


# (variant_name, cfg_overrides, microbatch_override)
CELLS = {
    "llama": ("llama-3.2-vision-90b", "train_4k", [
        ("outer_remat", {}, None),
        ("outer_remat_mb8", {}, 8),
        ("outer_remat_mb4", {}, 4),
        ("mb4_dots", {"remat": "dots"}, 4),
    ]),
    "qwen2": ("qwen2-7b", "train_4k", [
        ("remat_group7", {"remat_group": 7}, None),
        ("remat_group7_mb2", {"remat_group": 7}, 2),
        ("remat_group4_mb2", {"remat_group": 4}, 2),
        ("mb2_vocab32k", {"remat_group": 7, "vocab_chunk": 32768}, 2),
        ("mb2_dots", {"remat_group": 7, "remat": "dots"}, 2),
    ]),
    "mamba2": ("mamba2-370m", "train_4k", [
        ("chunk128", {"ssm_chunk": 128}, None),
        ("chunk64", {"ssm_chunk": 64}, None),
        ("chunk128_mb1", {"ssm_chunk": 128}, 1),
        ("baseline_mb1", {}, 1),
        ("ssd_bf16", {"ssm_intra": "bfloat16"}, None),
        ("ssd_bf16_group8", {"ssm_intra": "bfloat16",
                             "remat_group": 8}, None),
    ]),
    "grok": ("grok-1-314b", "train_4k", [
        ("ep_sharding", {"moe_ep": True}, None),
        ("remat_group8", {"remat_group": 8}, None),
        ("remat_group8_mb4", {"remat_group": 8}, 4),
    ]),
}


def layer_trips_variant(cfg) -> set:
    trips = {cfg.n_layers}
    if cfg.remat_group and cfg.n_layers % cfg.remat_group == 0:
        trips = {cfg.remat_group, cfg.n_layers // cfg.remat_group}
    if cfg.family == "vlm":
        g = cfg.cross_attn_every - 1
        trips = {cfg.n_layers // cfg.cross_attn_every, g}
    elif cfg.family == "hybrid":
        from repro.models.hybrid import scan_trips
        trips = scan_trips(cfg)
    elif cfg.family == "encdec":
        trips = {cfg.n_layers, cfg.n_encoder_layers}
    return trips


def run_variant(arch, shape_name, name, overrides, mb, out_dir):
    from repro.configs import SHAPES_BY_NAME, get_config
    from repro.distributed import sharding as shd
    from repro.launch.dryrun import build_cell
    from repro.launch.mesh import make_production_mesh
    from repro.perf.hlo_analysis import analyze_hlo_text
    from repro.perf.roofline import (HBM_BW, LINK_BW, PEAK_FLOPS,
                                     model_flops_per_device)

    cfg = get_config(arch)
    overrides = dict(overrides)
    if overrides.pop("moe_ep", False):
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, expert_sharding="ep"))
    ssm_chunk = overrides.pop("ssm_chunk", None)
    ssm_intra = overrides.pop("ssm_intra", None)
    cfg = _variant(cfg, ssm_chunk=ssm_chunk, ssm_intra=ssm_intra,
                   **overrides)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    rec = {"arch": arch, "shape": shape_name, "variant": name,
           "overrides": {**overrides,
                         **({"ssm_chunk": ssm_chunk} if ssm_chunk else {}),
                         **({"microbatches": mb} if mb else {})}}
    t0 = time.time()
    shd.set_activation_axes(shd.batch_axes(mesh), mesh=mesh)
    try:
        jitted, args, extra = build_cell(cfg, shape, mesh, microbatches=mb)
        rec.update(extra)
        with mesh:
            compiled = jitted.lower(*args).compile()
    finally:
        shd.set_activation_axes(None)
    ma = compiled.memory_analysis()
    parsed = analyze_hlo_text(compiled.as_text(),
                              layer_trips=layer_trips_variant(cfg))
    n_dev = mesh.devices.size
    mflops = model_flops_per_device(arch, shape_name, n_dev)
    mem_bytes = parsed.get("bytes_kernelized", parsed["bytes"])
    terms = {
        "compute_s": parsed["flops"] / PEAK_FLOPS,
        "memory_s": mem_bytes / HBM_BW,
        "memory_xla_s": parsed["bytes"] / HBM_BW,
        "collective_s": parsed["collective_bytes"] / LINK_BW,
    }
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    bound = terms[dominant]
    rec.update({
        "compile_s": round(time.time() - t0, 1),
        "mem_gib": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        / 2**30,
        "terms": terms,
        "dominant": dominant,
        "useful_flops_ratio": mflops / max(parsed["flops"], 1.0),
        "roofline_fraction": min((mflops / PEAK_FLOPS) / max(bound, 1e-30),
                                 1.0),
        "collectives_by_type": parsed["collectives_by_type"],
    })
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir,
                           f"{arch}_{shape_name}_{name}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    # Must land before jax initializes — which is why the driver path
    # only imports jax from inside run_variant()/main().
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append", default=None,
                    choices=list(CELLS))
    ap.add_argument("--out", default="experiments/hillclimb")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    cells = args.cell or list(CELLS)
    if args.list:
        for c in cells:
            print(c, CELLS[c][0], CELLS[c][1],
                  [v[0] for v in CELLS[c][2]])
        return
    for c in cells:
        arch, shape, variants = CELLS[c]
        for (name, overrides, mb) in variants:
            try:
                rec = run_variant(arch, shape, name, overrides, mb,
                                  args.out)
                t = rec["terms"]
                print(f"{arch} {shape} {name:20s} mem={rec['mem_gib']:6.2f}G "
                      f"C={t['compute_s']:7.2f} M={t['memory_s']:7.2f} "
                      f"N={t['collective_s']:7.2f} dom={rec['dominant']} "
                      f"roofline={rec['roofline_fraction']:.1%}",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"{arch} {shape} {name}: FAIL {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
