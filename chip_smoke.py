"""Chip smoke test: the training path, end to end, on one TPU chip.

Runs ``repro.launch.train`` in this process on the full mamba2-370m
config (368 M parameters; batch 8, seq 256, 6 steps) with a profiling
window over steps 1-4, an async checkpoint at step 3 and the final sync
one at step 6, then checks:

  * the run reached its last step and every logged loss is finite;
  * the loss at the initial parameters (the state step 0 trains from),
    computed on the chip on the first two rows of the first batch,
    matches ``models.loss_fn`` on the CPU backend within ``LOSS_RTOL``;
  * the newest checkpoint restores bit-equal to the final parameters;
  * the profiling window recorded POSIX reads of the token shards.

It prints the device, the run's wall time (compile included), peak HBM
and the profiler's counters.  Its last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

and it exits non-zero, without that line, when a check fails, when JAX
finds no TPU, or when the repo's sources are not next to it.

    python chip_smoke.py
"""
from __future__ import annotations

import glob
import json
import math
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORKDIR = ROOT / ".chip_smoke"         # token shards + checkpoints
ARCH = "mamba2-370m"
STEPS = 6
BATCH, SEQ = 8, 256
REF_ROWS = 2
LOSS_RTOL = 2e-2       # bf16 compute on the chip vs the CPU backend


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def smoke(workdir: Path, reduced: bool = False) -> None:
    """Runs the training path once and checks it; raises SmokeFailure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.tokens import token_batches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import build_parser, train
    from repro.models import init_params, loss_fn, param_count
    from repro.train.checkpoint import CheckpointManager

    print(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")

    shutil.rmtree(workdir, ignore_errors=True)    # a stale dir would resume
    argv = ["--arch", ARCH, "--batch", str(BATCH), "--seq", str(SEQ),
            "--steps", str(STEPS), "--checkpoint-every", "3",
            "--profile-window", "1", "4", "--workdir", str(workdir)]
    args = build_parser().parse_args(argv + (["--reduced"] if reduced
                                             else []))
    trainer, out = train(args)
    cfg = trainer.cfg
    params, _ = trainer.final_state
    print(f"model: {cfg.name} {'reduced' if reduced else 'full'}, "
          f"{param_count(params):,} params, microbatches "
          f"{trainer.tcfg.microbatches}")

    # -- the run
    for m in out["metrics"]:
        print(f"step {m['step']} loss {m['loss']!r} "
              f"grad_norm {m['grad_norm']!r}")
    print(f"wall_s {out['wall_s']!r} ({out['final_step']} steps, "
          f"compile included)")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    check(out["final_step"] == STEPS,
          f"run stopped at step {out['final_step']}, not {STEPS}")
    losses = [m["loss"] for m in out["metrics"]]
    check(len(losses) == STEPS, f"{len(losses)} losses logged, not {STEPS}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")

    # -- the loss at the initial params: chip vs CPU backend
    shards = sorted(glob.glob(os.path.join(workdir, "tokens", "*.jrec")))
    rows = next(token_batches(shards, BATCH, SEQ, cfg.vocab_size))[:REF_ROWS]
    params0 = init_params(cfg, jax.random.PRNGKey(trainer.tcfg.seed))
    loss0 = jax.jit(lambda p, t: loss_fn(p, cfg, {"tokens": t})[0])
    chip = float(loss0(params0, jnp.asarray(rows)))
    cpu = jax.devices("cpu")[0]
    ref = float(loss0(jax.device_put(params0, cpu),
                      jax.device_put(rows, cpu)))
    del params0
    rel = abs(chip - ref) / abs(ref)
    print(f"loss at init, first {REF_ROWS} rows: device {chip!r} "
          f"cpu {ref!r} rel_diff {rel!r} (rtol {LOSS_RTOL})")
    check(rel <= LOSS_RTOL, f"device loss {chip} vs cpu {ref}")

    # -- the newest checkpoint restores bit-equal
    ckpt = CheckpointManager(trainer.tcfg.checkpoint_dir)
    check(ckpt.latest_step() == STEPS,
          f"newest checkpoint is step {ckpt.latest_step()}")
    state, _ = ckpt.restore(target_tree={"params": params})
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
        b = np.asarray(jax.device_get(b))
        check(a.dtype == b.dtype and a.shape == b.shape
              and np.array_equal(np.ascontiguousarray(a).view(np.uint8),
                                 np.ascontiguousarray(b).view(np.uint8)),
              "restored params differ from the final params")
    print(f"checkpoint step {STEPS} restored bit-equal")

    # -- the profiling window saw the shard reads
    reports = out["profile_reports"]
    check(len(reports) == 1, f"{len(reports)} profile windows, not 1")
    rep = reports[0]
    for mod in (rep.posix, rep.stdio):
        print(f"profile {mod.module}: opens {mod.opens} reads {mod.reads} "
              f"bytes_read {mod.bytes_read} writes {mod.writes} "
              f"bytes_written {mod.bytes_written} fsyncs {mod.fsyncs}")
    shard_reads = sum(r.counters.get("POSIX_READS", 0)
                      for p, r in rep.per_file.items() if p.endswith(".jrec"))
    print(f"profile shard reads {shard_reads}")
    check(rep.posix.reads > 0 and rep.posix.bytes_read > 0 and shard_reads,
          "the profiling window recorded no POSIX reads of the token shards")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    try:
        smoke(WORKDIR)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
