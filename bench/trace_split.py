"""Runs one benchmark cell once with a profiler trace and prints where the
program's own tracing puts the window's time.

    python3 bench/trace_split.py --workload mamba2-370m.ckpt --seed 7 \
        --seconds 35

The run is ``run.py --trace 1``'s: the same cell, harness and window.
After it, the step's optimized HLO is taken from its jitted function
(compiled again, outside the window), and the trace is read with
``harness/progtrace.py``.  The last line on standard output is one JSON
object: ``correct``, every metric of the cell that its reader gives
(end-to-end and per-layer), and under ``program``:

* ``scope_ms``: device milliseconds per window step under each named
  scope, and ``groups_ms`` the same for the groups ``ssd``, ``proj``
  (in_proj + out_proj), ``loss``, ``optimizer``, with ``device_other``
  the busy time outside them;
* ``module_share``: the share of the busy time in each HLO module, and
  ``unmatched_share``, the share in ops of the step's module that its
  compiled text does not hold (nonzero: the text is not the program that
  ran);
* ``per_save_s``: writer and snapshot seconds per window save in each
  ``ckpt.*`` span; ``per_step_ms``: the step loop's milliseconds per
  window step in each ``train.*`` and ``profiler.*`` span;
* ``idle_gaps``: the longest device idle gaps, each named by the
  innermost program or harness span open in it;
* ``starved_steps``: ``train.starved_dispatches`` over the window.

A program without the scopes, spans or counter reads ``other``, no span
and null.  Without a TPU, or with fewer chips than the cell asks for, it
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
GROUPS = {"ssd": ("ssd",), "proj": ("in_proj", "out_proj"),
          "loss": ("loss",), "optimizer": ("optimizer",)}


class Watch:
    """What ``split`` keeps of the Trainer a cell builds: its jitted step
    with its first call's argument shapes, and its starvation counter as
    the window opens."""

    def __init__(self, trainer):
        import jax
        self.jitted = trainer._step_fn
        self.shapes = None
        self.telemetry = getattr(trainer, "telemetry", None)
        self.at_open = None
        feed = trainer.batches

        def step(*args):
            if self.shapes is None:
                self.shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding), args)
            return self.jitted(*args)

        watch = self

        class Batches:
            def __iter__(self):
                return self

            def __next__(self):
                if watch.at_open is None and feed.waiter.t0 is not None:
                    watch.at_open = watch.starved()
                return next(feed)

        trainer._step_fn, trainer.batches = step, Batches()

    def starved(self):
        if self.telemetry is None:
            return None
        return self.telemetry.snapshot()["counters"].get(
            "train.starved_dispatches", 0)


def split(cell, seed: int, seconds: float, workdir: str,
          t_start: float) -> dict:
    from harness import cell as cell_lib, devtrace, progtrace, spec
    from repro.train import trainer as trainer_mod

    watches = []
    base = trainer_mod.Trainer

    class Trainer(base):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            watches.append(Watch(self))

    trainer_mod.Trainer = Trainer
    try:
        outcome = cell_lib.run_cell(cell, seed, seconds, True, workdir,
                                    t_start)
    finally:
        trainer_mod.Trainer = base
    run, watch = outcome.run, watches[0]
    metrics = {}
    for m in cell.metrics:
        value = spec.reader(m.name)(run)
        if value is not None:
            metrics[m.name] = value

    path = devtrace.find_xplane(os.path.join(workdir, "trace"))
    trace = progtrace.read(path)
    hlo = progtrace.parse_hlo(
        watch.jitted.lower(*watch.shapes).compile().as_text())
    n = len(run.window_steps)
    prog = {"hlo_module": hlo.module}
    groups = progtrace.scope_times(trace, run.t0, run.t_end, hlo, GROUPS)
    each = progtrace.scope_times(trace, run.t0, run.t_end, hlo)
    if groups is not None:
        prog["groups_ms"] = {("device_other" if g == "other" else g):
                             1e3 * v / n for g, v in groups.items()}
        prog["scope_ms"] = {s: 1e3 * v / n for s, v in each.items()}
        prog.update(_modules(trace, run.t0, run.t_end, hlo))
    spans = progtrace.program_spans(trace, run.t0)
    window = [s for s in spans if run.t0 <= s.t0 < run.t_end]
    saves = [r for r in run.saves if r["step"] >= run.warmup]
    per = defaultdict(float)
    for s in window:
        per[s.name] += s.t1 - s.t0
    if saves:
        prog["per_save_s"] = {k: v / len(saves) for k, v in sorted(per.items())
                              if k.startswith("ckpt.")}
    prog["per_step_ms"] = {k: 1e3 * v / n for k, v in sorted(per.items())
                           if not k.startswith("ckpt.")}
    prog["span_counts"] = dict(Counter(s.name for s in window))
    named = devtrace.reduce(
        devtrace.load(path), run.t0, run.t_end,
        [(s.name, s.t0, s.t1) for s in run.spans.items]
        + [(s.name, s.t0, s.t1) for s in spans])
    prog["idle_gaps"] = named.idle_gaps if named else None
    end = watch.starved()
    prog["starved_steps"] = (None if end is None or watch.at_open is None
                             else end - watch.at_open)
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
