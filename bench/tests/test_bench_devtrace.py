"""The trace reduction: device busy and idle time over the window, the
top device ops, and idle gaps named by the host span open in them."""
import glob
import os

import pytest

from harness import devtrace
from harness.devtrace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")


def _ops(plane, spans):
    return [Event(plane, devtrace.OPS_LINE, name, a, b - a)
            for name, a, b in spans]


def test_reduce_unions_ops_and_names_gaps():
    # trace clock = host clock + 100 s
    events = [Event("/host:CPU", "waiter", devtrace.WINDOW_MARK, 101.0, 0.0)]
    events += _ops("/device:TPU:0", [("fusion.1", 101.0, 101.4),
                                     ("fusion.2", 101.2, 101.5),   # overlaps
                                     ("convolution", 101.8, 102.0),
                                     ("fusion.1", 102.5, 103.5)])  # clipped
    spans = [("input", 1.5, 1.7), ("dispatch", 1.4, 1.9),
             ("save_stall", 2.0, 2.5)]
    s = devtrace.reduce(events, t0=1.0, t_end=3.0, spans=spans)
    assert s.window_s == pytest.approx(2.0)
    assert s.busy_s == pytest.approx(0.5 + 0.2 + 0.5)
    assert s.device_ops[0][0] == "fusion.1"
    assert s.device_ops[0][1] == pytest.approx(0.4 + 0.5)
    gaps = {name: round(sec, 6) for name, sec in s.idle_gaps}
    assert gaps == {"input": 0.3, "save_stall": 0.5}


def test_two_devices_average_their_busy_time():
    events = [Event("/host:CPU", "w", devtrace.WINDOW_MARK, 0.0, 0.0)]
    events += _ops("/device:TPU:0", [("a", 0.0, 1.0)])
    events += _ops("/device:TPU:1", [("a", 0.0, 0.5)])
    s = devtrace.reduce(events, 0.0, 1.0, [])
    assert s.devices == 2 and s.busy_s == pytest.approx(0.75)


def test_no_device_ops_gives_nothing():
    events = [Event("/host:CPU", "w", devtrace.WINDOW_MARK, 0.0, 0.0)]
    assert devtrace.reduce(events, 0.0, 1.0, []) is None


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
    events = devtrace.load(devtrace.find_xplane(str(tmp_path)))
    assert [e.name for e in events if e.plane.startswith("/host")] == [
        devtrace.WINDOW_MARK]


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.xplane.pb"))))
def test_a_recorded_chip_trace_slice(path):
    """A slice of a chip trace of the mamba2-370m steady cell: a few
    steps on one TPU v5 lite, kept small."""
    events = devtrace.load(path)
    ops = [e for e in events if e.line == devtrace.OPS_LINE]
    marks = [e for e in events if e.name == devtrace.WINDOW_MARK]
    assert ops and len(marks) == 1
    t0 = marks[0].start_s
    t_end = max(e.start_s + e.dur_s for e in ops)
    s = devtrace.reduce(events, t0, t_end, [])
    assert 0 < s.busy_s <= s.window_s
    assert len(s.device_ops) == devtrace.TOP
    assert all(sec > 0 for _, sec in s.idle_gaps)
