"""Serving launcher: batched greedy decoding over the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--checkpoint", default=None,
                    help="checkpoint dir to load params from")
    args = ap.parse_args()

    import time

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.serve.engine import Request, ServeEngine

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    params = init_params(cfg, jax.random.PRNGKey(0))
    if args.checkpoint:
        from repro.train.checkpoint import CheckpointManager
        state, _ = CheckpointManager(args.checkpoint).restore(
            target_tree={"params": params})
        params = state["params"]

    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(2, 12))).astype(np.int32),
                    max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in done)
    for i, r in enumerate(done):
        print(f"req{i:02d} ({len(r.prompt)} prompt toks) -> {r.out}")
    print(f"{len(done)} requests, {total} tokens, {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
