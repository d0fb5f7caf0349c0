"""End-to-end training driver: token shards on disk -> instrumented data
pipeline -> jitted train step (grad accumulation) -> fault-tolerant
checkpoints, with a tf-Darshan profiling window feeding the advisor.

    PYTHONPATH=src python examples/train_lm.py --arch qwen2-7b --steps 30
    PYTHONPATH=src python examples/train_lm.py --arch mamba2-370m \
        --steps 200 --batch 8 --seq 256      # ~the 100M-scale run

Reduced configs are the default, so the example runs in seconds on any
backend, the CPU included; --full trains the published config (one TPU
v5e holds mamba2-370m with its optimizer state).
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="use the full (assigned) config, not the reduced")
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.data.synthetic import make_token_shards
    from repro.data.tokens import token_batches
    from repro.models import param_count, init_params
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch, reduced=not args.full)
    ws = args.workdir or tempfile.mkdtemp(prefix="train_lm_")
    shards = make_token_shards(os.path.join(ws, "tokens"), n_shards=4,
                               docs_per_shard=64,
                               vocab_size=cfg.vocab_size)
    batches = token_batches(shards, args.batch, args.seq, cfg.vocab_size)

    tcfg = TrainerConfig(
        steps=args.steps,
        checkpoint_every=max(args.steps // 3, 1),
        checkpoint_dir=os.path.join(ws, "checkpoints"),
        log_every=max(args.steps // 10, 1),
        microbatches=args.microbatches,
        profile_first=1, profile_last=min(args.steps - 1, 11),
        profile_every=5,
    )
    trainer = Trainer(cfg, tcfg, batches)
    import jax
    n = param_count(init_params(cfg, jax.random.PRNGKey(0)))
    print(f"arch={args.arch} ({'full' if args.full else 'reduced'}), "
          f"params={n / 1e6:.1f}M, steps={args.steps}")
    out = trainer.run()
    for m in out["metrics"]:
        print(f"  step {m['step']:5d}  loss={m['loss']:.4f}  "
              f"grad_norm={m['grad_norm']:.3f}")
    print(f"wall: {out['wall_s']:.1f}s; "
          f"checkpoints in {tcfg.checkpoint_dir}")
    for i, rep in enumerate(out["profile_reports"]):
        print(f"  profile window {i}: POSIX "
              f"{rep.posix_bandwidth_mb_s:.1f} MB/s, "
              f"{rep.posix.reads} reads, "
              f"meta {rep.posix.meta_time_s * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
