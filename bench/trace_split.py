"""Runs one benchmark cell once with a profiler trace and prints where the
program's own tracing puts the window's time.

    python3 bench/trace_split.py --workload mamba2-370m.ckpt --seed 7 \
        --seconds 35

The run is ``run.py --trace 1``'s: the same cell, harness and window,
and the same reading of the program's tracing (``harness/cell.py``, with
``harness/progtrace.py``).  The last line on standard output is one JSON
object: ``correct``, every metric of the cell that its reader gives
(end-to-end and per-layer), and under ``program``:

* ``scope_ms``: device milliseconds per window step under each named
  scope, with ``other`` the busy time outside them (the cell's metrics
  give the groups: ``ssd_ms``, ``proj_ms``, ..., ``device_other_ms``);
* ``module_share``: the share of the busy time in each HLO module, and
  ``unmatched_share``, the share in ops of the step's module that its
  compiled text does not hold (nonzero: the text is not the program that
  ran);
* ``per_save_s``: writer and snapshot seconds per window save in each
  ``ckpt.*`` span (``Run.per_save_s``); ``per_step_ms``: the step loop's milliseconds per
  window step in each ``train.*`` and ``profiler.*`` span;
* ``idle_gaps``: the longest device idle gaps, each named by the
  innermost program or harness span open in it;
* ``starved_steps``: ``train.starved_dispatches`` over the window.

A program without the scopes or spans reads ``other`` and no span.
Without a TPU, or with fewer chips than the cell asks for, it
exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
WORK = os.path.join(BENCH, ".work")


def split(cell, seed: int, seconds: float, workdir: str,
          t_start: float) -> dict:
    from harness import cell as cell_lib, devtrace, progtrace, spec

    outcome = cell_lib.run_cell(cell, seed, seconds, True, workdir, t_start)
    run = outcome.run
    metrics = {}
    for m in cell.metrics:
        value = spec.reader(m.name)(run)
        if value is not None:
            metrics[m.name] = value

    prog = {"hlo_module": run.hlo.module}
    if run.scope_unions is not None:
        prog["scope_ms"] = {s: run.scope_ms((s,)) for s in progtrace.SCOPES}
        prog["scope_ms"]["other"] = (run.scope_ms()
                                     - run.scope_ms(progtrace.SCOPES))
        trace = progtrace.read(devtrace.find_xplane(
            os.path.join(workdir, "trace")))
        prog.update(_modules(trace, run.t0, run.t_end, run.hlo))
    n = len(run.window_steps)
    window = [s for s in run.program_spans if run.t0 <= s.t0 < run.t_end]
    per = defaultdict(float)
    for s in window:
        per[s.name] += s.t1 - s.t0
    names = sorted({s.name for s in run.program_spans
                    if s.name.startswith("ckpt.")})
    per_save = {k: run.per_save_s(k) for k in names}
    prog["per_save_s"] = {k: v for k, v in per_save.items() if v is not None}
    prog["per_step_ms"] = {k: 1e3 * v / n for k, v in sorted(per.items())
                           if not k.startswith("ckpt.")}
    prog["span_counts"] = dict(Counter(s.name for s in window))
    prog["idle_gaps"] = run.trace.idle_gaps if run.trace else None
    prog["starved_steps"] = run.starved
    return {"correct": all(x.ok for x in outcome.numbers),
            "attempted": outcome.attempted, "metrics": metrics,
            "device": outcome.device, "program": prog}


def _modules(trace, t0: float, t_end: float, hlo) -> dict:
    """Shares of the window's op time (summed, not unioned) by HLO module,
    and of ops of the step's module that its text does not name."""
    shift = trace.mark - t0
    lo, hi = t0 + shift, t_end + shift
    by_module, unmatched, total = Counter(), 0.0, 0.0
    for op in trace.ops:
        d = min(op.start_s + op.dur_s, hi) - max(op.start_s, lo)
        if d <= 0 or op.control:
            continue
        total += d
        by_module[op.module] += d
        if op.module in (None, hlo.module) and op.name not in hlo.names:
            unmatched += d
    total = total or 1.0
    return {"module_share": {str(k): v / total
                             for k, v in by_module.most_common(8)},
            "unmatched_share": unmatched / total}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from harness import spec
    cell = spec.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"trace_split: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    workdir = os.path.join(WORK, args.workload)
    try:
        out = split(cell, args.seed, args.seconds, workdir, T_START)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
