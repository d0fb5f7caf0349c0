"""Readings that set the limits of the training comparison, taken with the
plain reference put in the program's place (no program runs here):

* the control: the reference computed a precision step lower (float8
  operands, one scale per tensor) against the float32 reference;
* the fault "half of the batch left out, the mean taken over the rest":
  the reference trained on the first half of each batch's rows.

Each is read at the cell's own size, on the seeds given, and printed as
one JSON line per seed and reading, with each number beside the cell's
limit and whether the run would be ``correct`` under those limits (each
reading has to come out not correct).  The benchmark's own runs never
run this.

    python3 bench/readings.py --workload mamba2-370m.steady --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(cell, seed: int) -> list:
    import numpy as np

    from harness import cell as cell_lib, check, spec, traffic
    tr, model = cell.traffic, cell.config["model"]
    opt, limits = cell.config["optimizer"], cell.config["limits"]
    ref = spec.reference(cell.config)
    corpus = traffic.make_corpus(tr, model["vocab_size"], seed)
    first = []
    for tokens, _ in traffic.expected_batches(corpus, tr["batch"], tr["seq"],
                                              model["vocab_size"]):
        first.append(tokens)
        if len(first) == check.REF_STEPS:
            break
    key = cell_lib.seed_key(seed)
    base = check.reference_readings(ref, model, opt, key, first)
    half = [t[:len(t) // 2] for t in first]
    out = []
    for name, batches, precision in (("control_fp8", first, "fp8"),
                                     ("fault_half_batch", half, "f32")):
        t = time.perf_counter()
        got = check.reference_readings(ref, model, opt, key, batches,
                                       precision)
        nums = check.training_numbers(got, base, limits)
        out.append({"cell": cell.name, "seed": seed, "reading": name,
                    "seconds": time.perf_counter() - t,
                    "correct": all(n.ok for n in nums),
                    "numbers": {n.name: {"value": n.value, "limit": n.limit,
                                         "ok": n.ok} for n in nums},
                    "loss": got["loss"].tolist(),
                    "ref_loss": base["loss"].tolist(),
                    "ref_grad_norm": base["grad_norm"].tolist(),
                    "tiny_leaves": int(np.sum(base["leaf_grad"] < check.TINY_GRAD
                                              * np.median(base["leaf_grad"]))),
                    "leaves": check.leaf_report(ref, model, got, base)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from harness import spec
    cell = spec.resolve(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        for line in readings(cell, seed):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
