"""Training launcher: JRecord token shards -> instrumented token batches
-> jitted, donated train step -> async checkpoints, with an optional
tf-Darshan profiling window over a range of steps.

One process per host; JAX maps it onto the host's devices.  The full
configs are sized for a TPU; ``--reduced`` runs the same path at toy
widths on any backend, the CPU included.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
        --steps 20 --profile-window 1 4 --workdir run

Re-running with the same ``--workdir`` resumes from its newest
checkpoint.  ``train(args)`` is the in-process entry point (``chip_smoke.py``
drives it).
"""
from __future__ import annotations

import argparse
import os


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", default=None,
                    help="dir of JRecord token shards (made if missing)")
    ap.add_argument("--workdir", default="run")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = auto-resolve for the device's HBM")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="0 = every steps/5 steps")
    ap.add_argument("--profile-window", type=int, nargs=2, default=None,
                    metavar=("FIRST", "LAST"))
    return ap


def train(args: argparse.Namespace):
    """Runs one training job; returns ``(trainer, trainer.run() result)``."""
    import glob

    import jax

    from repro.configs import get_config
    from repro.data.synthetic import make_token_shards
    from repro.data.tokens import token_batches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train.optimizer import for_model
    from repro.train.train_step import resolve_microbatches
    from repro.train.trainer import Trainer, TrainerConfig

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    data_dir = args.data or os.path.join(args.workdir, "tokens")
    shards = sorted(glob.glob(os.path.join(data_dir, "*.jrec")))
    if not shards:
        shards = make_token_shards(data_dir, n_shards=4, docs_per_shard=64,
                                   vocab_size=cfg.vocab_size)

    # data-parallel sharding of input files across hosts
    n_hosts, host_id = jax.process_count(), jax.process_index()
    shards = shards[host_id::n_hosts] or shards

    mb = args.microbatches or resolve_microbatches(
        cfg, args.batch, args.seq, data_shards=1)
    window = args.profile_window or (-1, -1)
    tcfg = TrainerConfig(
        steps=args.steps,
        checkpoint_every=args.checkpoint_every or max(args.steps // 5, 1),
        checkpoint_dir=os.path.join(args.workdir, "checkpoints"),
        log_every=max(args.steps // 20, 1),
        microbatches=mb,
        profile_first=window[0],
        profile_last=window[1],
        profile_every=5,
    )
    batches = token_batches(shards, args.batch, args.seq, cfg.vocab_size)
    trainer = Trainer(cfg, tcfg, batches,
                      ocfg=for_model(cfg, lr=args.lr))
    return trainer, trainer.run()


def main(argv=None) -> None:
    trainer, out = train(build_parser().parse_args(argv))
    for m in out["metrics"]:
        print(f"step {m['step']:6d} loss={m['loss']:.4f} "
              f"lr={m['lr']:.2e} gnorm={m['grad_norm']:.3f}")
    print(f"done: {out['final_step']} steps in {out['wall_s']:.1f}s; "
          f"checkpoints: {trainer.tcfg.checkpoint_dir}")
    for i, rep in enumerate(out["profile_reports"]):
        print(f"profile[{i}]: {rep.posix_bandwidth_mb_s:.1f} MB/s POSIX, "
              f"{rep.posix.reads} reads / {rep.posix.opens} opens")


if __name__ == "__main__":
    main()
