"""The window's close, and the readers of the program's tracing, on the
CPU: the feed's cap on the saves a window starts, a whole tiny run that
reaches it, and each new reader on a synthetic ``Run``."""
import types

import numpy as np
import pytest

import tiny
from harness import cell as cell_lib, check, devtrace, progtrace, spec

WARMUP = 5


def _close(monkeypatch, step_s, seconds, every, cap, batches=1000):
    """Drives a ``Feed`` as the step loop does, on a clock that moves
    ``step_s`` per batch, saving after every ``every``-th step.  Returns
    the batches the feed gave and the steps at which saves started."""
    clock = [0.0]
    monkeypatch.setattr(cell_lib, "time", types.SimpleNamespace(
        perf_counter=lambda: clock[0]))
    waiter, spans = cell_lib.Waiter(WARMUP), cell_lib.Spans()
    waiter.t0 = 0.0
    feed = cell_lib.Feed(iter([np.zeros(2, np.int32)] * batches), waiter,
                         spans, seconds, None, every, cap)
    for step in range(batches):
        try:
            next(feed)
        except cell_lib.WindowClosed:
            break
        clock[0] += step_s
        if (step + 1) % every == 0:
            with spans("save_stall", step + 1):
                pass
    return len(feed.crcs), [s.step for s in spans.of("save_stall")]


@pytest.mark.parametrize("step_s, cap, given, saves", [
    # today's step: the time closes the window, capped or not
    (0.555, None, 64, [55]),
    (0.555, 1, 64, [55]),
    # a step fast enough to reach more saves: the cap closes the window
    # at the batch of step 109, whose dispatch would save at 110 (or of
    # step 164, with two saves allowed)
    (0.125, None, 280, [55, 110, 165, 220, 275]),
    (0.125, 1, 109, [55]),
    (0.125, 2, 164, [55, 110]),
])
def test_the_feed_closes_the_window_before_a_save_past_the_cap(
        monkeypatch, step_s, cap, given, saves):
    assert _close(monkeypatch, step_s, 35.0, 55, cap) == (given, saves)


def test_saves_of_set_up_do_not_count_against_the_cap(monkeypatch):
    # saves at 3 (set-up: before step WARMUP) and 6; the batch of step 8
    # would start a save at 9
    assert _close(monkeypatch, 0.01, 35.0, 3, 1) == (8, [3, 6])


@pytest.fixture
def capped_run(monkeypatch, tmp_path):
    """The tiny ckpt cell, saving every 10 steps, with a window far longer
    than the cap lets it run."""
    from repro.launch import compile_cache
    monkeypatch.setattr(spec, "peaks", lambda kind: {"bf16_flops": 1e12})
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    cell = tiny.tiny_cell("mamba2-370m", "ckpt", "mamba2-370m.ckpt")
    cell.traffic["checkpoint_every"] = 10
    return cell_lib.run_cell(cell, 2**31 + 17, 600.0, False,
                             str(tmp_path / "work"), 0.0)


def test_a_fast_step_leaves_one_save_in_the_window(capped_run):
    out, run = capped_run, capped_run.run
    assert not [n.name for n in out.numbers if not n.ok], \
        check.format_numbers(out.numbers)
    # the save at step 10, then the batch of step 19 is refused: steps
    # 0..18 ran, 5..18 in the window
    assert [r["step"] for r in run.saves] == [10]
    assert out.attempted == 19 and run.window_steps == range(WARMUP, 19)
    commit = run.spans.of("save_write")[0].t1
    assert run.t_end == max(run.ready[-1], commit)
    # the save commits while the window's steps still run: no tail
    assert commit < run.ready[-1] and run.t_end == run.ready[-1]
    assert run.window_s < 60.0
    value = spec.reader("save_commit_s")(run)
    assert 0 < value < run.window_s


# A synthetic traced window: host [1, 2] s, trace clock = host + 100 s,
# two steps; the step's module holds five scoped ops, one unscoped op,
# and a second program runs beside it.
HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.1 () -> f32[8] {
  %a = f32[8]{0} dot(), metadata={op_name="jit(step)/ssd/dot_general"}
  %b = f32[8]{0} dot(), metadata={op_name="jit(step)/in_proj/dot_general"}
  %c = f32[8]{0} dot(), metadata={op_name="jit(step)/out_proj/dot_general"}
  %d = f32[8]{0} fusion(), metadata={op_name="jit(step)/transpose(jvp(loss))/x"}
  %e = f32[8]{0} add(), metadata={op_name="jit(step)/optimizer/add"}
  %f = f32[8]{0} conv(), metadata={op_name="jit(step)/conv/conv"}
  ROOT %g = f32[8]{0} copy(), metadata={op_name="jit(step)/embed/gather"}
}
"""
OPS = [("a", 101.00, 101.20, None), ("b", 101.20, 101.30, None),
       ("c", 101.30, 101.35, None), ("d", 101.35, 101.45, None),
       ("e", 101.45, 101.50, None), ("f", 101.50, 101.58, None),
       ("g", 101.58, 101.60, None), ("a", 101.60, 101.64, "jit_checksums"),
       ("a", 101.70, 101.86, None)]


def _traced_run(program_spans=(), save_spans=()):
    ops = [progtrace.Op("/device:TPU:0", n, m, a, b - a, False)
           for n, a, b, m in OPS]
    trace = progtrace.Trace(ops, [], 101.0)
    hlo = progtrace.parse_hlo(HLO)
    spans = cell_lib.Spans()
    spans.items = [cell_lib.Span(*s) for s in save_spans]
    run = cell_lib.Run(
        cell="synthetic", tokens_per_step=8, warmup=WARMUP, t_start=0.0,
        t0=1.0, t_end=2.0, ready=[0.5] * WARMUP + [1.5, 2.0],
        flops_per_step=1, peak_flops=1.0, spans=spans, saves=[],
        profiled=True, starved=3,
        program_spans=[progtrace.HostSpan(n, ("host", 0), a, b)
                       for n, a, b in program_spans],
        hlo=hlo, scope_unions=progtrace.scope_unions(trace, 1.0, 2.0, hlo))
    run.trace = devtrace.reduce(ops, 101.0, 1.0, 2.0, [])
    return run


SCOPE_METRICS = {"ssd_ms": 180.0, "proj_ms": 75.0, "loss_ms": 50.0,
                 "optimizer_ms": 25.0, "conv_ms": 40.0,
                 "device_other_ms": 30.0}


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_each_scope_reader_reads_its_scopes_per_window_step(name):
    assert spec.reader(name)(_traced_run()) == pytest.approx(
        SCOPE_METRICS[name])


def test_the_scope_metrics_sum_to_the_device_step():
    run = _traced_run()
    step_ms = spec.reader("device_step_ms")(run)
    assert step_ms == pytest.approx(400.0)
    assert sum(spec.reader(n)(run) for n in SCOPE_METRICS) == \
        pytest.approx(step_ms)


def test_the_program_readers_read_nothing_from_an_untraced_run():
    run = _traced_run()
    run.trace = run.scope_unions = None
    for name in SCOPE_METRICS:
        assert spec.reader(name)(run) is None, name
    for name in ("save_serialize_s", "save_fsync_s"):
        assert spec.reader(name)(run) is None, name


def test_starved_steps_reads_the_programs_counter():
    assert spec.reader("starved_steps")(_traced_run()) == 3


@pytest.mark.parametrize("name, value", [("save_serialize_s", 0.3),
                                         ("save_fsync_s", 0.2)])
def test_the_writer_readers_read_window_saves_only(name, value):
    """Two saves: one of set-up (step 3), one of the window (step 10);
    each span counts for the save whose writer's time holds it."""
    run = _traced_run(
        program_spans=[("ckpt.serialize", 0.10, 0.30),
                       ("ckpt.serialize", 1.20, 1.35),
                       ("ckpt.serialize", 1.40, 1.55),
                       ("ckpt.fsync", 0.30, 0.35),
                       ("ckpt.fsync", 1.60, 1.80),
                       ("ckpt.snapshot", 1.05, 1.15)],
        save_spans=[("save_stall", 0.05, 0.08, 3),
                    ("save_write", 0.08, 0.40, 3),
                    ("save_stall", 1.00, 1.18, 10),
                    ("save_write", 1.18, 1.90, 10)])
    assert spec.reader(name)(run) == pytest.approx(value)
    assert run.per_save_s("ckpt.snapshot") == pytest.approx(0.1)
    assert run.per_save_s("ckpt.commit") is None
