"""Runs one benchmark cell once on the accelerator and prints its result.

    python3 bench/run.py --workload mamba2-370m.steady --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` (see ``harness/spec.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  The last
lines on standard error, and the result's last key ``check``, give each
number that decided ``correct`` beside its limit.  The last line on
standard output is the result, one JSON object.  Without a TPU, or with
fewer chips than the cell asks for, it exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(BENCH, ".work")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result(cell, outcome, trace: bool) -> dict:
    from harness import spec
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics:
        if m.kind != kind:
            continue
        value = spec.reader(m.name)(outcome.run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    out = {"correct": all(n.ok for n in outcome.numbers),
           "attempted": outcome.attempted, "failed": outcome.failed,
           "metrics": metrics, "device": outcome.device}
    if trace and outcome.run.trace is not None:
        out["breakdown"] = {"device_ops": outcome.run.trace.device_ops,
                            "idle_gaps": outcome.run.trace.idle_gaps}
    out["check"] = {n.name: {"value": n.value, "limit": n.limit}
                    for n in outcome.numbers}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from harness import spec
    cell = spec.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", file=sys.stderr)

    from harness import check, cell as cell_lib
    workdir = os.path.join(WORK, args.workload)
    try:
        outcome = cell_lib.run_cell(cell, args.seed, args.seconds,
                                    bool(args.trace), workdir, T_START)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    line = json.dumps(result(cell, outcome, bool(args.trace)))
    for text in check.format_numbers(outcome.numbers):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
