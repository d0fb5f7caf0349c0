"""Runs one cell once.

The Trainer is built from the cell's files the way
``repro.launch.train.train`` builds it: the same ``TrainerConfig``
fields, ``token_batches`` over JRecord shards, and the profiler's step
window with its restarts every ``profile_every`` steps.  The weights are
the benchmark's (``reference.<name>.init``), made on the device in one
jitted call from the seed, and handed to ``Trainer._run_span``, the
program's own step loop, which then runs set-up and the window in one
call:

* set-up: ``warmup_steps`` steps, the first three of which the check
  reads (losses, the first step's optimizer moment, the change after
  three steps);
* the window: from the moment the last set-up step's outputs are ready,
  for ``seconds``; the feed then refuses the next batch (``WindowClosed``),
  which also keeps the loop off its final synchronous save.  Where the
  mix caps its saves (``saves_in_window``), the feed also refuses the
  batch of a step after which the program would start one save more
  than that, however early, so a faster step never adds a second save's
  stall and commit to the window.  The window ends when the last
  dispatched step's outputs are ready, or when the last save started in
  it has committed, whichever is later.

Steps are timed from output-ready to output-ready by a waiter thread
that blocks on each step's metrics in order, so the loop itself never
syncs.  The harness records its spans around each call into a layer
(``next(batches)``, the step dispatch, the profiler hooks, the saves) as
``jax.profiler.TraceAnnotation``s too, so a traced run can name what the
host was doing in each device idle gap.

A traced run also reads the program's own tracing (``harness/progtrace``):
its host spans name the idle gaps too, and the step's device time is split
by the step's named scopes (from the step's optimized HLO, taken after the
window from the compile cache).  Every run counts the program's
``train.starved_dispatches`` (``Trainer.telemetry``) over the window's
steps.
"""
from __future__ import annotations

import os
import queue
import shutil
import sys
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from harness import check as check_lib
from harness import devtrace, progtrace, spec as spec_lib, traffic as traffic_lib

JOIN_S = 600.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class WindowClosed(Exception):
    """The feed's answer to the first batch asked for after the window."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def seed_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def model_config(model: dict):
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    kw = dict(model)
    kw["ssm"] = SSMConfig(**kw.get("ssm", {}))
    kw["moe"] = MoEConfig(**kw.get("moe", {}))
    return ModelConfig(**kw)


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    step: int


class Spans:
    def __init__(self):
        self.items: List[Span] = []

    @contextmanager
    def __call__(self, name: str, step: int = -1):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                self.items.append(Span(name, t0, time.perf_counter(), step))

    def of(self, name: str, steps=None) -> List[Span]:
        return [s for s in self.items if s.name == name
                and (steps is None or s.step in steps)]


class Waiter(threading.Thread):
    """Blocks on each step's metrics in order and notes when they are
    ready.  The ready time of step ``warmup - 1`` opens the window."""

    def __init__(self, warmup: int):
        super().__init__(name="bench-waiter", daemon=True)
        self.q: "queue.SimpleQueue" = queue.SimpleQueue()
        self.warmup = warmup
        self.ready: List[float] = []
        self.first: Dict[int, tuple] = {}
        self.t0: Optional[float] = None
        self.lead = 0             # steps dispatched ahead as the window opens
        self.error: Optional[BaseException] = None

    def run(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            step, metrics = item
            try:
                jax.block_until_ready(metrics)
                t = time.perf_counter()
                if step == self.warmup - 1:
                    with jax.profiler.TraceAnnotation(devtrace.WINDOW_MARK):
                        self.t0 = t
                    self.lead = self.q.qsize()
                self.ready.append(t)
                if step < check_lib.REF_STEPS:
                    self.first[step] = (float(metrics["loss"]),
                                        float(metrics["grad_norm"]))
            except Exception as e:        # reported by the main thread
                self.error = e
                self.ready.append(time.perf_counter())


class Feed:
    """Wraps the program's batch iterator: times each ``next``, notes the
    crc of each batch, starts the trace before the window, and closes
    the window: at ``seconds`` after it opened, or at the batch of a step
    after which the program (saving after every ``save_every``-th step)
    would start a save beyond the first ``saves_in_window`` of the
    window's steps."""

    def __init__(self, it, waiter: Waiter, spans: Spans, seconds: float,
                 trace_dir: Optional[str], save_every: int = 0,
                 saves_in_window: Optional[int] = None):
        self.it, self.waiter, self.spans = it, waiter, spans
        self.seconds, self.trace_dir = seconds, trace_dir
        self.save_every, self.saves_in_window = save_every, saves_in_window
        self.crcs: List[int] = []

    def __iter__(self):
        return self

    def __next__(self):
        i = len(self.crcs)
        t0 = self.waiter.t0
        if t0 is not None and time.perf_counter() >= t0 + self.seconds:
            raise WindowClosed
        if self.saves_full(i):
            raise WindowClosed
        if self.trace_dir and i == self.waiter.warmup - 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with self.spans("input", i):
            batch = next(self.it)
        self.crcs.append(traffic_lib.batch_crc(batch))
        return batch

    def saves_full(self, step: int) -> bool:
        """Whether dispatching ``step`` would start a save past the cap."""
        if self.saves_in_window is None or (step + 1) % self.save_every:
            return False
        started = sum(s.step >= self.waiter.warmup
                      for s in self.spans.of("save_stall"))
        return started >= self.saves_in_window


@dataclass
class Run:
    """What the metric readers (``bench/metrics``) read."""
    cell: str
    tokens_per_step: int
    warmup: int
    t_start: float
    t0: float
    t_end: float
    ready: List[float]
    flops_per_step: int
    peak_flops: float
    spans: Spans
    saves: List[dict]
    profiled: bool
    trace: Optional[devtrace.Summary] = None
    # the program's own tracing, in traced runs: its host spans on the
    # host clock, the window's device time by scope and the step's HLO;
    # and the starved dispatches among the window's steps
    program_spans: List[progtrace.HostSpan] = field(default_factory=list)
    scope_unions: Optional[progtrace.Unions] = None
    hlo: Optional[progtrace.Hlo] = None
    starved: Optional[int] = None

    @property
    def window_steps(self) -> range:
        return range(self.warmup, len(self.ready))

    @property
    def step_times(self) -> np.ndarray:
        r = np.asarray(self.ready)
        return np.diff(r[self.warmup - 1:])

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def state_ready(self, step: int) -> float:
        """When the state that a save at ``step`` holds was ready: the
        outputs of the step before it."""
        return self.ready[step - 1]

    def scope_ms(self, scopes=None) -> Optional[float]:
        """Device milliseconds per window step in which an op of the
        step's program under one of ``scopes`` ran (any op where None)."""
        if self.scope_unions is None:
            return None
        return (1e3 * progtrace.device_s(self.scope_unions, scopes)
                / len(self.window_steps))

    def per_save_s(self, name: str) -> Optional[float]:
        """Seconds per window save in the program's span ``name``: its
        spans whose midpoint lies in the step thread's or the writer's
        time of a save that the window's steps started (the harness's
        ``save_stall`` and ``save_write`` spans)."""
        saves = [s for s in self.spans.items if s.step >= self.warmup
                 and s.name in ("save_stall", "save_write")]
        inside = [p.t1 - p.t0 for p in self.program_spans if p.name == name
                  and any(w.t0 <= (p.t0 + p.t1) / 2 <= w.t1 for w in saves)]
        writes = sum(s.name == "save_write" for s in saves)
        if not inside or not writes:
            return None
        return sum(inside) / writes


@dataclass
class Outcome:
    run: Run
    numbers: List[check_lib.Number]
    attempted: int
    failed: int
    device: dict = field(default_factory=dict)
    window_compiles: int = 0


def _state_fn(ref, model: dict, ocfg):
    from repro.train.optimizer import init_opt_state

    def make(key):
        params = ref.init(model, key)
        return params, init_opt_state(ocfg, params)
    return jax.jit(make)


def run_cell(cell: spec_lib.Cell, seed: int, seconds: float, trace: bool,
             workdir: str, t_start: float) -> Outcome:
    from repro.data import tokens as tokens_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.train import trainer as trainer_mod
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import resolve_microbatches

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    tr, model = cell.traffic, cell.config["model"]
    cfg = model_config(model)
    ocfg = OptimizerConfig(**cell.config["optimizer"])
    ref = spec_lib.reference(cell.config)
    batch, seq, warmup = tr["batch"], tr["seq"], tr["warmup_steps"]
    every = tr["profile_every"]

    shutil.rmtree(workdir, ignore_errors=True)
    corpus = traffic_lib.make_corpus(tr, cfg.vocab_size, seed)
    shards = traffic_lib.write_shards(corpus, os.path.join(workdir, "tokens"))
    steps = tr["steps"]
    tcfg = trainer_mod.TrainerConfig(
        steps=steps,
        checkpoint_every=tr["checkpoint_every"] or max(steps // 5, 1),
        checkpoint_dir=os.path.join(workdir, "checkpoints"),
        keep_checkpoints=tr["keep_checkpoints"],
        log_every=max(steps // 20, 1),
        microbatches=resolve_microbatches(cfg, batch, seq, data_shards=1),
        profile_first=warmup if every else -1,
        profile_last=steps if every else -1,
        profile_every=every,
        seed=seed)
    trace_dir = os.path.join(workdir, "trace") if trace else None
    spans, waiter = Spans(), Waiter(warmup)
    feed = Feed(tokens_mod.token_batches(shards, batch, seq, cfg.vocab_size),
                waiter, spans, seconds, trace_dir, tcfg.checkpoint_every,
                tr.get("saves_in_window"))
    trainer = trainer_mod.Trainer(cfg, tcfg, feed, ocfg=ocfg)
    key = seed_key(seed)
    reads = _instrument(trainer, waiter, spans, ref, model, key)
    params, opt_state = _state_fn(ref, model, ocfg)(key)
    if tr["checkpoint_every"]:        # compile the save's checksum now
        reads["checksums"]({"params": params, "opt": opt_state})
    jax.block_until_ready(opt_state)
    compiles: List[float] = []

    def on_compile(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    log(f"microbatches {tcfg.microbatches}; state ready at "
        f"{time.perf_counter() - t_start:.3f} s")

    waiter.start()
    try:
        trainer._run_span(params, opt_state, 0)
        raise RuntimeError("the step loop ended before the window closed")
    except WindowClosed:
        pass
    finally:
        del params, opt_state
        waiter.q.put(None)
        waiter.join(JOIN_S)
        try:
            trainer.ckpt.wait()
        finally:
            reads["restore"]()
            jax.monitoring.unregister_event_duration_listener(on_compile)
    if waiter.error is not None:
        raise waiter.error
    starved = (_starved(trainer) - reads["starved_at_open"]
               if "starved_at_open" in reads else None)
    commits = [s.t1 for s in spans.of("save_write")]
    t_end = max([waiter.ready[-1]] + commits)
    cb = trainer.profiler
    if cb is not None and cb.session._active:
        cb.session.stop()
    if trace_dir:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace stopped in {time.perf_counter() - t:.3f} s")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int((dev.memory_stats() or {}).get(
                  "peak_bytes_in_use", 0))}
    n_steps = len(waiter.ready)
    in_window = sum(t >= waiter.t0 for t in compiles)
    times = np.diff(waiter.ready[warmup - 1:])
    log(f"window: steps {warmup}..{n_steps - 1}, "
        f"{waiter.ready[-1] - waiter.t0!r} s to the last step, "
        f"{t_end - waiter.t0!r} s in all; {in_window} compiles in it; "
        f"saves at steps {[r['step'] for r in reads['saves']]}; "
        f"{waiter.lead} steps queued as it opened; longest step "
        f"{times.max():.3f} s, step {warmup + int(times.argmax())}")

    run = Run(cell=cell.name, tokens_per_step=batch * seq, warmup=warmup,
              t_start=t_start, t0=waiter.t0, t_end=t_end,
              ready=list(waiter.ready),
              flops_per_step=ref.flops_per_step(model, batch, seq),
              peak_flops=spec_lib.peaks(dev.device_kind)["bf16_flops"],
              spans=spans, saves=reads["saves"], profiled=cb is not None,
              starved=starved)
    if trace_dir:
        _read_trace(run, devtrace.find_xplane(trace_dir), reads)
        if run.trace is not None:
            device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)

    numbers = check(cell, corpus, trainer, feed, waiter, reads, ref, key)
    return Outcome(run=run, numbers=numbers, attempted=n_steps, failed=0,
                   device=device, window_compiles=in_window)


def _read_trace(run: Run, path: str, reads: dict) -> None:
    """Puts the traced window on ``run``: the device's busy time and its
    gaps, named by the harness's and the program's spans, and the step's
    device time by scope, from the step's optimized HLO."""
    t = time.perf_counter()
    trace = progtrace.read(path)
    t_read = time.perf_counter()
    run.program_spans = progtrace.program_spans(trace, run.t0)
    run.trace = devtrace.reduce(
        trace.ops if trace.on_device else [], trace.mark, run.t0, run.t_end,
        [(s.name, s.t0, s.t1) for s in run.spans.items]
        + [(s.name, s.t0, s.t1) for s in run.program_spans])
    t_reduce = time.perf_counter()
    text = reads["step_fn"].lower(*reads["shapes"]).compile().as_text()
    run.hlo = progtrace.parse_hlo(text)
    t_hlo = time.perf_counter()
    run.scope_unions = progtrace.scope_unions(trace, run.t0, run.t_end, run.hlo)
    log(f"trace: {len(trace.ops)} ops read in {t_read - t:.3f} s, reduced "
        f"in {t_reduce - t_read:.3f} s; step HLO {run.hlo.module} in "
        f"{t_hlo - t_reduce:.3f} s; split by scope in "
        f"{time.perf_counter() - t_hlo:.3f} s")


def _starved(trainer) -> int:
    return trainer.telemetry.snapshot()["counters"].get(
        "train.starved_dispatches", 0)


def _instrument(trainer, waiter: Waiter, spans: Spans, ref, model: dict, key):
    """Wraps the step, the profiler hooks, the saves and the writer's file
    writes.  Returns the dict the check reads from: the optimizer's first
    moment after step one (on the host), the change norms after step
    three, each save's checksums and the bytes the writer wrote; and,
    for the trace, the jitted step with its first call's argument shapes
    and the starvation counter at the last set-up step's dispatch."""
    reads: Dict[str, object] = {"saves": [], "step_fn": trainer._step_fn}
    step_fn = trainer._step_fn
    change = check_lib.change_norms(ref, model)
    calls = [0]

    def step(params, opt_state, batch):
        i = calls[0]
        if i == 0:
            reads["shapes"] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                (params, opt_state, batch))
        if i == waiter.warmup - 1:
            reads["starved_at_open"] = _starved(trainer)
        with spans("dispatch", i):
            out = step_fn(params, opt_state, batch)
        calls[0] += 1
        waiter.q.put((i, out[2]))
        if i == 0:       # set-up: the first moment waits on the host
            reads["moments"] = jax.device_get(
                jax.tree.leaves(check_lib.first_moments(out[1])))
        if i == check_lib.REF_STEPS - 1:
            reads["change"] = change(out[0], key)
        return out

    trainer._step_fn = step
    cb = trainer.profiler
    if cb is not None:
        begin, end = cb.on_step_begin, cb.on_step_end

        def on_begin(s):
            with spans("profiler", s):
                begin(s)

        def on_end(s):
            with spans("profiler", s):
                end(s)

        cb.on_step_begin, cb.on_step_end = on_begin, on_end

    ckpt = trainer.ckpt
    save_async, save = ckpt.save_async, ckpt.save
    sums = reads["checksums"] = jax.jit(check_lib.checksums)

    def wrapped_async(s, tree, extra=None):
        t_call = time.perf_counter()
        with spans("save_stall", s):
            out = save_async(s, tree, extra)
        reads["saves"].append({
            "step": s, "t_call": t_call, "checksums": sums(tree),
            "bytes": int(sum(x.nbytes for x in jax.tree.leaves(tree)))})
        return out

    def wrapped_save(s, tree, extra=None):
        with spans("save_write", s):
            return save(s, tree, extra)

    ckpt.save_async, ckpt.save = wrapped_async, wrapped_save

    from repro.train import checkpoint as ckpt_mod
    write_atomic = ckpt_mod._write_atomic
    reads["written"] = []

    def wrapped_write(path, data):
        write_atomic(path, data)
        reads["written"].append(len(data))

    ckpt_mod._write_atomic = wrapped_write
    reads["restore"] = lambda: setattr(ckpt_mod, "_write_atomic",
                                       write_atomic)
    return reads


def check(cell, corpus, trainer, feed: Feed, waiter: Waiter, reads: dict,
          ref, key) -> List[check_lib.Number]:
    """Runs once the window has closed and the program's state is freed."""
    tr, model = cell.traffic, cell.config["model"]
    batch, seq, warmup = tr["batch"], tr["seq"], tr["warmup_steps"]
    vocab = model["vocab_size"]
    n = len(feed.crcs)
    expected, first = [], []
    for i, (tokens, _) in enumerate(traffic_lib.expected_batches(
            corpus, batch, seq, vocab)):
        if i >= n:
            break
        expected.append(traffic_lib.batch_crc(tokens))
        if i < check_lib.REF_STEPS:
            first.append(tokens)
    numbers = [check_lib.count(
        "batches_mismatched", sum(a != b for a, b in zip(feed.crcs, expected))
        + abs(n - len(expected)))]

    opt = cell.config["optimizer"]
    prog = {"loss": np.asarray([waiter.first[i][0]
                                for i in range(check_lib.REF_STEPS)]),
            "grad_norm": np.asarray([waiter.first[i][1]
                                     for i in range(check_lib.REF_STEPS)]),
            "grad": [m / (1 - opt["b1"]) for m in reads["moments"]],
            "leaf_change": np.asarray(reads["change"])}
    t = time.perf_counter()
    refr = check_lib.reference_readings(ref, model, opt, key, first)
    log(f"reference: {time.perf_counter() - t:.3f} s")
    prog["leaf_grad"] = check_lib.host_leaf_norms(prog["grad"])
    numbers += check_lib.training_numbers(prog, refr, cell.config["limits"])
    for line in check_lib.leaf_report(ref, model, prog, refr):
        log(line)

    ckpt_bytes = sum(reads["written"])
    if reads["saves"]:
        kept = reads["saves"][-trainer.tcfg.keep_checkpoints:]
        numbers.append(check_lib.count(
            "ckpt_leaves_mismatched",
            checkpoint_mismatches(trainer.tcfg.checkpoint_dir, kept)))
    if trainer.profiler is not None:
        recorded = traffic_lib.shard_counters(trainer.profiler.reports,
                                              corpus.paths)
        io = traffic_lib.io_of(corpus, batch, seq, vocab, range(warmup, n))
        numbers += check_lib.profiler_numbers(
            recorded, io, ckpt_bytes,
            tr.get("limits", {}).get("stdio_write_missed_share"))
    return numbers


def checkpoint_mismatches(directory: str, saves: List[dict]) -> int:
    """Leaves of the saves the checkpoint directory must still hold whose
    committed file differs from the device state it was saved from."""
    import json
    bad = 0
    for rec in saves:
        sums = [int(x) for x in jax.device_get(rec["checksums"])]
        d = os.path.join(directory, f"step_{rec['step']:010d}")
        try:
            with open(os.path.join(d, "MANIFEST.json"), "rb") as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            bad += len(sums)
            continue
        entries = manifest.get("entries", [])
        if manifest.get("step") != rec["step"] or len(entries) != len(sums):
            bad += len(sums)
            continue
        for entry, want in zip(entries, sums):
            try:
                arr = np.load(os.path.join(d, entry["file"]),
                              allow_pickle=False)
            except (OSError, ValueError):
                bad += 1
                continue
            if (list(arr.shape) != entry["shape"]
                    or check_lib.host_checksum(arr) != want):
                bad += 1
    return bad
