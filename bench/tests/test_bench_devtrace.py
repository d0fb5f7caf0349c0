"""The trace reduction: device busy and idle time over the window, the
top device ops, and idle gaps named by the host span open in them."""
import glob
import os

import pytest

from harness import devtrace, progtrace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ops(plane, spans):
    return [progtrace.Op(plane, name, None, a, b - a, name.startswith("while"))
            for name, a, b in spans]


def test_reduce_unions_ops_and_names_gaps():
    # trace clock = host clock + 100 s; the mark at 101 s
    ops = _ops("/device:TPU:0", [("while.3", 101.0, 102.0),    # holds others
                                 ("fusion.1", 101.0, 101.4),
                                 ("fusion.2", 101.2, 101.5),   # overlaps
                                 ("convolution", 101.8, 102.0),
                                 ("fusion.1", 102.5, 103.5)])  # clipped
    spans = [("input", 1.5, 1.7), ("dispatch", 1.4, 1.9),
             ("save_stall", 2.0, 2.5)]
    s = devtrace.reduce(ops[1:], 101.0, t0=1.0, t_end=3.0, spans=spans)
    assert s.window_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(0.5 + 0.2 + 0.5)
    assert s.device_ops[0][0] == "fusion.1"
    assert s.device_ops[0][1] == pytest.approx(0.4 + 0.5)
    gaps = {name: round(sec, 6) for name, sec in s.idle_gaps}
    assert gaps == {"input": 0.3, "save_stall": 0.5}
    # an op that holds others adds to the busy time, not to the top ops
    s = devtrace.reduce(ops, 101.0, t0=1.0, t_end=3.0, spans=spans)
    assert s.busy_s == pytest.approx(1.0 + 0.5)
    assert "while.3" not in {name for name, _ in s.device_ops}


def test_two_devices_average_their_busy_time():
    ops = (_ops("/device:TPU:0", [("a", 0.0, 1.0)])
           + _ops("/device:TPU:1", [("a", 0.0, 0.5)]))
    s = devtrace.reduce(ops, 0.0, 0.0, 1.0, [])
    assert s.devices == 2 and s.busy_s == pytest.approx(0.75)


def test_no_device_ops_gives_nothing():
    assert devtrace.reduce([], 0.0, 0.0, 1.0, []) is None
    assert devtrace.reduce(_ops("/device:TPU:0", [("a", 0.0, 1.0)]), None,
                           0.0, 1.0, []) is None


def test_load_reads_the_mark_from_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_MARK):
            pass
        jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = progtrace.read(devtrace.find_xplane(str(tmp_path)))
    assert trace.mark is not None
    # the CPU backend has no device plane: its ops are host events
    assert trace.ops and not trace.on_device


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb"))))
def test_a_recorded_chip_trace_slice(path):
    """A slice of a chip trace of the mamba2-370m steady cell: a few
    steps on one TPU v5 lite, kept small."""
    trace = progtrace.read(path)
    assert trace.ops and trace.on_device and trace.mark is not None
    t_end = max(op.start_s + op.dur_s for op in trace.ops)
    s = devtrace.reduce(trace.ops, trace.mark, trace.mark, t_end, [])
    assert 0 < s.busy_s <= s.window_s
    assert len(s.device_ops) == devtrace.TOP
    assert all(sec > 0 for _, sec in s.idle_gaps)
