"""Trainer: the end-to-end loop tying together the instrumented data
pipeline, the jitted train step, fault-tolerant checkpointing, and the
tf-Darshan profiling/auto-tuning hooks.

Fault tolerance: the loop auto-resumes from the newest checkpoint on
start; a ``FailureInjector`` (tests) or any exception inside the step is
survived by restoring the last checkpoint and continuing.  The profiling
callback mirrors tf-Darshan's "automatic" mode: profile a window of
steps, then let the advisor adjust reader parallelism / propose staging.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.session import StepCallback
from repro.models import init_params, shared_sites
from repro.models.layers import attention_paths
from repro.models.ssm import ssd_paths
from repro.obs.metrics import MetricsRegistry
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptimizerConfig, for_model, init_opt_state
from repro.train.train_step import make_train_step


@dataclass
class TrainerConfig:
    steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "checkpoints"
    checkpoint_async: bool = True
    keep_checkpoints: int = 3
    log_every: int = 10
    microbatches: int = 1
    profile_first: int = -1           # -1 = no profiling window
    profile_last: int = -1
    profile_every: Optional[int] = None
    seed: int = 0


class FailureInjector:
    """Raises at a chosen step once — used to test checkpoint/restart."""

    def __init__(self, fail_at_step: Optional[int] = None):
        self.fail_at_step = fail_at_step
        self.fired = False

    def maybe_fail(self, step: int) -> None:
        if (self.fail_at_step is not None and step == self.fail_at_step
                and not self.fired):
            self.fired = True
            raise RuntimeError(f"injected failure at step {step}")


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainerConfig,
                 batches: Iterator[np.ndarray],
                 ocfg: Optional[OptimizerConfig] = None,
                 failure: Optional[FailureInjector] = None,
                 extra_batch: Optional[dict] = None,
                 fleet_reporter=None,
                 profiler=None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.ocfg = ocfg or for_model(cfg)
        self.batches = batches
        self.failure = failure
        self.extra_batch = extra_batch or {}
        self.ckpt = CheckpointManager(tcfg.checkpoint_dir,
                                      keep=tcfg.keep_checkpoints)
        self.metrics_log: list = []
        self.final_state = None            # (params, opt_state) after run()
        # train.starved_dispatches: steps dispatched after the previous
        # step had already finished, so the device sat idle waiting on
        # the host (read without blocking, through ``is_ready``).
        # model.ssd_kernel: 1 where the compiled step's SSD scans took
        # the fused Pallas kernel, 0 where they fell back;
        # model.attention_kernel: the same for its full-sequence
        # attention calls (0 where it has none); and model.shared_sites:
        # the hybrid shared block's sites in the compiled step (all set
        # as the step is traced).
        self.telemetry = MetricsRegistry()
        self._starved = self.telemetry.counter("train.starved_dispatches")
        self._step_fn = jax.jit(
            self._record_model_paths(make_train_step(
                cfg, self.ocfg, microbatches=tcfg.microbatches)),
            donate_argnums=(0, 1))
        # Profiling goes through the repro.profiler façade: pass a
        # Profiler (or ProfilerOptions) with a step_window, or use the
        # legacy TrainerConfig.profile_first/last fields, which build an
        # equivalent façade under the hood.
        self.profiler_facade = self._make_facade(profiler)
        self.profiler: Optional[StepCallback] = (
            self.profiler_facade.step_callback()
            if self.profiler_facade is not None else None)
        # closed-loop tuning: throttle-checkpoint actions need the
        # checkpoint manager bound on the applier, io-chunk actions the
        # ingest engine's adaptive chunker (no-op if tune is off)
        if self.profiler_facade is not None \
                and getattr(self.profiler_facade.options, "tune", False):
            from repro.io.adaptive import default_chunker
            self.profiler_facade.bind_tune(checkpoint_manager=self.ckpt,
                                           io_chunker=default_chunker())
        # Distributed profiling: a repro.fleet.RankReporter profiles this
        # process's whole run and ships it to the FleetCollector (the
        # shipping — reporter.ship / ship_socket — is the caller's call,
        # after run() returns).
        self.fleet_reporter = fleet_reporter

    def _record_model_paths(self, step):
        ssd = self.telemetry.gauge("model.ssd_kernel")
        attn = self.telemetry.gauge("model.attention_kernel")
        sites = self.telemetry.gauge("model.shared_sites")

        @functools.wraps(step)
        def train_step(params, opt_state, batch):
            with ssd_paths() as ssd_taken, attention_paths() as attn_taken:
                out = step(params, opt_state, batch)
            for gauge, taken in ((ssd, ssd_taken), (attn, attn_taken)):
                gauge.set(1.0 if taken and all(taken) else 0.0)
            sites.set(float(shared_sites(self.cfg)))
            return out
        return train_step

    def _make_facade(self, profiler):
        from repro.profiler import Profiler, ProfilerOptions
        if profiler is not None:
            if isinstance(profiler, ProfilerOptions):
                profiler = Profiler(profiler)
            if profiler.options.step_window is None:
                raise ValueError(
                    "Trainer profiling needs ProfilerOptions("
                    "step_window=(first, last))")
            return profiler
        if self.tcfg.profile_first < 0:
            return None
        return Profiler(ProfilerOptions(
            step_window=(self.tcfg.profile_first, self.tcfg.profile_last),
            step_every=self.tcfg.profile_every))

    # ------------------------------------------------------------------ init
    def init_state(self):
        params = init_params(self.cfg, jax.random.PRNGKey(self.tcfg.seed))
        opt_state = init_opt_state(self.ocfg, params)
        return params, opt_state, 0

    def _restore_or_init(self):
        latest = self.ckpt.latest_step()
        if latest is None:
            return self.init_state()
        params, opt_state, _ = self.init_state()
        state, extra = self.ckpt.restore(
            latest, target_tree={"params": params, "opt": opt_state})
        return state["params"], state["opt"], extra.get("step", latest)

    # ------------------------------------------------------------------ run
    def run(self) -> dict:
        params, opt_state, start_step = self._restore_or_init()
        step = start_step
        t_begin = time.perf_counter()
        if self.fleet_reporter is not None:
            self.fleet_reporter.start()
        try:
            while step < self.tcfg.steps:
                try:
                    step = self._run_span(params, opt_state, step)
                    break
                except RuntimeError as e:
                    if "injected failure" not in str(e):
                        raise
                    # failure recovery: reload newest checkpoint, continue
                    self.ckpt.wait()
                    params, opt_state, step = self._restore_or_init()
            self.ckpt.wait()
        finally:
            rank_report = None
            if self.fleet_reporter is not None \
                    and self.fleet_reporter.session._active:
                rank_report = self.fleet_reporter.stop()
        wall = time.perf_counter() - t_begin
        return {"final_step": step, "wall_s": wall,
                "metrics": self.metrics_log,
                "rank_report": rank_report,
                "profile_reports": (self.profiler.reports
                                    if self.profiler else []),
                "telemetry": self.telemetry.snapshot(),
                # unified repro.profiler.Report views of the same windows
                "reports": (self.profiler_facade.reports
                            if self.profiler_facade is not None else [])}

    def _run_span(self, params, opt_state, step) -> int:
        """The step loop.  Each step is a ``StepTraceAnnotation`` and its
        host phases ``train.*`` spans, so a profiler trace lines the host
        up with the device work of the same step."""
        metrics = None
        while step < self.tcfg.steps:
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                if self.profiler:
                    self.profiler.on_step_begin(step)
                with jax.profiler.TraceAnnotation("train.input"):
                    batch_tokens = next(self.batches)
                with jax.profiler.TraceAnnotation("train.to_device"):
                    batch = {"tokens": jnp.asarray(batch_tokens)}
                batch.update(self.extra_batch)
                if self.failure:
                    self.failure.maybe_fail(step)
                if metrics is not None and metrics["loss"].is_ready():
                    self._starved.inc()
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    params, opt_state, metrics = self._step_fn(
                        params, opt_state, batch)
                if self.profiler:
                    self.profiler.on_step_end(step)
                step += 1
                if step % self.tcfg.log_every == 0 or step == self.tcfg.steps:
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    self.metrics_log.append(m)
                if step % self.tcfg.checkpoint_every == 0 \
                        or step == self.tcfg.steps:
                    with jax.profiler.TraceAnnotation("train.save"):
                        self._save(step, params, opt_state)
        # keep final state reachable for callers/tests
        self.final_state = (params, opt_state)
        return step

    def _save(self, step: int, params, opt_state) -> None:
        tree = {"params": params, "opt": opt_state}
        if self.tcfg.checkpoint_async and step != self.tcfg.steps:
            self.ckpt.save_async(step, tree, extra={"step": step})
        else:
            self.ckpt.wait()     # drain any in-flight async save
            self.ckpt.save(step, tree, extra={"step": step})
