"""Reduction of a profiler trace's device ops (``progtrace.read``) to the
device's busy and idle time over the measured window, per-step device
time, and the ``breakdown`` of the result line.

Device operations are the events on the ``XLA Ops`` line of each
``/device:`` plane.  The harness's own spans are kept on the host's
``perf_counter`` clock; the mark ``bench.window_start``, written into the
trace at the window's first instant, gives the offset between the two.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_MARK = "bench.window_start"
OPS_LINE = "XLA Ops"
TOP = 10
CONTROL_FLOW = (" while(", " conditional(", " call(")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


@dataclass
class Summary:
    busy_s: float
    window_s: float
    device_ops: List[list] = field(default_factory=list)
    idle_gaps: List[list] = field(default_factory=list)
    devices: int = 1


def reduce(ops: Sequence, mark: Optional[float], t0: float, t_end: float,
           spans: List[Tuple[str, float, float]]) -> Optional[Summary]:
    """``ops`` are the device's ops (``progtrace.Op``: plane, instruction
    name, start and duration on the trace clock, and whether it holds
    others), ``mark`` the window mark's instant on the trace clock.
    ``t0``/``t_end`` and ``spans`` (name, start, end) are on the host's
    ``perf_counter`` clock, and ``t0`` is the instant the window mark was
    written.  Returns None where there is no device op or no mark."""
    if mark is None or not ops:
        return None
    shift = mark - t0                             # trace clock - host clock
    lo, hi = t0 + shift, t_end + shift
    window = t_end - t0
    by_plane: Dict[str, List[Tuple[float, float]]] = {}
    per_op: Dict[str, float] = {}
    for e in ops:
        iv = by_plane.setdefault(e.plane, [])
        a, b = max(e.start_s, lo), min(e.start_s + e.dur_s, hi)
        if b > a:
            iv.append((a, b))
            if not e.control:
                per_op[e.name] = per_op.get(e.name, 0.0) + (b - a)
    busy_total = 0.0
    gaps: List[Tuple[float, float]] = []
    for iv in by_plane.values():
        busy = union(iv)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(by_plane)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    named = [[host_activity(spans, (a + b) / 2 - shift), b - a]
             for a, b in top_gaps]
    return Summary(busy_s=busy_total / n, window_s=window,
                   device_ops=[[k, v / n] for k, v in top_ops],
                   idle_gaps=named, devices=n)


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), ...`` -> ``fusion.12``.  Ops
    that hold others (while, conditional, call) are left out of the top
    list (``reduce``), whose entries would otherwise count their bodies
    twice."""
    return op.split(" = ", 1)[0].lstrip("%")


def host_activity(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost harness span open at ``t``, or ``other``."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "other"
