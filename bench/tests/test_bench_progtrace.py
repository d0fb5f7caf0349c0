"""The program's own tracing read off a trace: its host spans on the
host clock, and device time split by the step's named scopes."""
import re
import time

import jax
import pytest

import tiny
from harness import devtrace, progtrace


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ssd/dot_general", "ssd"),
    ("jit(train_step)/jvp()/while/body/closed_call/checkpoint/in_proj/"
     "dot_general", "in_proj"),
    ("jit(train_step)/transpose(jvp(loss))/while/body/closed_call/"
     "bsd,dv->bsv/dot_general", "loss"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/jvp()/while/body/closed_call/checkpoint/out_proj/"
     "conv/pad", "conv"),
    ("jit(train_step)/jvp()/while/body/add", None),
    ("jit(train_step)/jvp()/jit(softplus)/log1p", None),
])
def test_an_op_belongs_to_its_innermost_scope(op_name, scope):
    assert progtrace.scope_of(op_name) == scope


def test_every_matmul_of_the_tiny_step_has_a_scope():
    """The mamba2 step at a tiny size, with the cell's remat and chunked
    loss, compiled for the CPU: forward, backward and recomputed
    matmuls all fall under a scope."""
    from harness import cell as cell_lib
    from repro.models import init_params
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import make_train_step

    config = tiny.tiny_config("mamba2-370m")
    cfg = cell_lib.model_config(config["model"])
    ocfg = OptimizerConfig(**config["optimizer"])
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: init_opt_state(ocfg, params))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jax.numpy.int32)}
    text = jax.jit(make_train_step(cfg, ocfg)).lower(
        params, opt, batch).compile().as_text()

    scopes = progtrace.hlo_scopes(text)
    matmuls = [line for line in text.splitlines()
               if re.search(r" (dot|convolution)\(", line)]
    names = [line.split(" = ", 1)[0].split()[-1].lstrip("%")
             for line in matmuls]
    assert names and all(n in scopes for n in names), [
        n for n in names if n not in scopes]
    assert {scopes[n] for n in names} == {"in_proj", "ssd", "out_proj",
                                          "loss"}
    assert any("transpose(" in m for m in matmuls)
    assert any("rematted_computation" in m for m in matmuls)
    assert set(scopes.values()) == set(progtrace.SCOPES)


HLO = """\
HloModule jit_step, entry_computation_layout={(f32[8,8]{1,0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(step)/jvp()/while/body/ssd/neg"}
  ROOT %exponential.1 = f32[8]{0} exponential(%negate.1), metadata={op_name="jit(step)/jvp()/while/body/ssd/exp"}
}

ENTRY %main.9 (a: f32[8,8]) -> f32[8] {
  %a = f32[8,8]{1,0} parameter(0)
  %dot.1 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/jvp()/while/body/in_proj/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dot.3 = f32[8,8]{1,0} dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp())/out_proj/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(loss))/reduce_max"}
  %add.5 = f32[8]{0} add(%fusion.2, %fusion.4), metadata={op_name="jit(step)/optimizer/add"}
  %copy.6 = f32[8]{0} copy(%add.5)
  %add.8 = f32[8]{0} add(%copy.6, %copy.6), metadata={op_name="jit(step)/jvp()/while/body/add"}
  ROOT %while.7 = f32[8]{0} while(%add.8), condition=%cond, body=%body, metadata={op_name="jit(step)/ssd/while"}
}
"""


def _op(name, a, b, module="jit_step"):
    return progtrace.Op("/device:TPU:0", name, module, a, b - a,
                        name.startswith("while"))


def _split(trace, hlo, groups=None, t0=1.0, t_end=2.0):
    """Seconds of the window under each group of scopes, and ``other``,
    the busy time outside them all."""
    unions = progtrace.scope_unions(trace, t0, t_end, hlo)
    if unions is None:
        return None
    groups = groups or {s: (s,) for s in progtrace.SCOPES}
    out = {g: progtrace.device_s(unions, ss) for g, ss in groups.items()}
    out["other"] = progtrace.device_s(unions) - progtrace.device_s(
        unions, [s for ss in groups.values() for s in ss])
    return out


def test_scope_times_split_the_busy_time():
    hlo = progtrace.parse_hlo(HLO)
    assert hlo.module == "jit_step"
    assert hlo.control == {"while.7"}
    assert hlo.scopes == {"negate.1": "ssd", "exponential.1": "ssd",
                          "dot.1": "in_proj", "fusion.2": "ssd",
                          "dot.3": "out_proj", "fusion.4": "loss",
                          "add.5": "optimizer", "copy.6": "optimizer",
                          "while.7": "ssd"}      # add.8 names no scope
    # trace clock = host clock + 100 s; the window is host [1, 2]
    ops = [_op("while.7", 101.0, 101.9),          # holds the others
           _op("dot.1", 100.9, 101.1),            # clipped at the window
           _op("fusion.2", 101.1, 101.3),
           _op("fusion.2", 101.25, 101.35),       # overlaps: one union
           _op("dot.3", 101.4, 101.5),
           _op("fusion.4", 101.5, 101.6),
           _op("add.5", 101.6, 101.65),
           _op("copy.6", 101.65, 101.7),          # made by the compiler
           _op("add.8", 101.7, 101.8),
           _op("dot.3", 102.1, 102.2)]            # after the window
    trace = progtrace.Trace(ops, [], 101.0)
    groups = {"ssd": ("ssd",), "proj": ("in_proj", "out_proj"),
              "loss": ("loss",), "optimizer": ("optimizer",)}
    t = _split(trace, hlo, groups)
    assert t["ssd"] == pytest.approx(0.25)
    assert t["proj"] == pytest.approx(0.2)
    assert t["loss"] == pytest.approx(0.1)
    assert t["optimizer"] == pytest.approx(0.1)
    busy = devtrace.reduce(ops, 101.0, 1.0, 2.0, []).busy_s
    assert busy == pytest.approx(0.9)
    assert t["other"] == pytest.approx(0.25)
    assert sum(t.values()) == pytest.approx(busy)
    each = _split(trace, hlo)
    assert set(each) == set(progtrace.SCOPES) | {"other"}
    assert each["in_proj"] == pytest.approx(0.1)
    assert each["conv"] == 0.0


def test_ops_of_another_module_count_as_other():
    """A second program in the window (the save's checksum) reuses the
    step's instruction names; its ops fall under no scope."""
    hlo = progtrace.parse_hlo(HLO)
    step = [_op("dot.1", 101.0, 101.2), _op("fusion.2", 101.2, 101.3)]
    other = [_op("dot.1", 101.5, 101.6, "jit_checksums"),
             _op("fusion.2", 101.6, 101.8, "jit_checksums")]
    t = _split(progtrace.Trace(step + other, [], 101.0), hlo)
    assert t["in_proj"] == pytest.approx(0.2)
    assert t["ssd"] == pytest.approx(0.1)
    assert t["other"] == pytest.approx(0.3)
    assert sum(t.values()) == pytest.approx(0.6)
    # with no module in the trace, names alone decide
    bare = [progtrace.Op(o.plane, o.name, None, o.start_s, o.dur_s, False)
            for o in step + other]
    t = _split(progtrace.Trace(bare, [], 101.0), hlo)
    assert t["in_proj"] == pytest.approx(0.3)
    assert t["other"] == pytest.approx(0.0)


def test_no_device_ops_gives_nothing():
    hlo = progtrace.parse_hlo(HLO)
    assert progtrace.scope_unions(progtrace.Trace([], [], 0.0), 0.0, 1.0,
                                  hlo) is None
    assert progtrace.scope_unions(
        progtrace.Trace([_op("dot.1", 0.0, 0.5)], [], None), 0.0, 1.0,
        hlo) is None


def test_program_spans_are_put_on_the_host_clock(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_MARK):
            t0 = time.perf_counter()
        time.sleep(0.01)
        with jax.profiler.StepTraceAnnotation("train", step_num=7):
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("train.input"):
                time.sleep(0.02)
            b = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.input"):
                pass
    finally:
        jax.profiler.stop_trace()
    trace = progtrace.read(devtrace.find_xplane(str(tmp_path)))
    spans = progtrace.program_spans(trace, t0)
    assert [s.name for s in spans] == ["train", "train.input"]
    step, inp = spans
    assert step.thread == inp.thread
    assert a - 2e-3 <= inp.t0 < inp.t1 <= b + 2e-3
    assert step.t0 <= inp.t0 and inp.t1 <= step.t1
