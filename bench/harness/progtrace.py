"""The program's own tracing, read off a profiler trace (``.xplane.pb``),
with the device ops that ``devtrace.reduce`` reduces: the harness reads
each trace once, here.

* Host spans: the step loop's ``jax.profiler.StepTraceAnnotation``
  (``train``) and ``TraceAnnotation``s named ``train.*``, ``profiler.*``
  and ``ckpt.*`` (``repro.train.trainer``, ``repro.core.session``,
  ``repro.train.checkpoint``), put on the host's ``perf_counter`` clock
  with the window mark's shift, as ``devtrace.reduce`` puts its own.
* Device scopes: each device op of the step's HLO module takes the
  ``jax.named_scope`` (``SCOPES``, set in ``repro.models`` and
  ``repro.train.train_step``) in the ``op_name`` metadata of its
  instruction in the step's optimized HLO text
  (``jitted.lower(...).compile().as_text()``); the union of the window's
  device time under each scope follows.

An op is keyed by its HLO module as well as its instruction name: other
programs run in the window (the save's checksum, the snapshot's
transfers) and reuse names such as ``fusion.12``; their ops fall under
no scope.  Device ops are the ``XLA Ops`` events of each ``/device:``
plane, whose module is the ``XLA Modules`` event around them, else the
event's ``hlo_module`` stat; with no device plane (the CPU backend) they
are the host events that carry ``hlo_op`` and ``hlo_module`` stats.

Scopes nest under JAX's own names: a scanned, rematted scope under
``jax.grad`` reads ``.../transpose(jvp())/while/body/closed_call/
checkpoint/rematted_computation/ssd/dot_general``, and a scope that opens
a differentiated function is folded into the transform, as in
``transpose(jvp(loss))``.  An op belongs to the innermost path component
that is, or wraps, a scope name.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Tuple

from harness import devtrace

STEP_SPAN = "train"
SPAN_PREFIXES = ("train.", "profiler.", "ckpt.")
SCOPES = ("in_proj", "conv", "ssd", "out_proj", "loss", "optimizer")
MODULES_LINE = "XLA Modules"
CONTROL_OPCODES = ("while", "conditional", "call")

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([\w\-]+)\(")


@dataclass
class HostSpan:
    name: str
    thread: Tuple[str, int]       # (host plane, line index): one thread
    t0: float                     # trace clock, or host clock once shifted
    t1: float


@dataclass
class Op:
    plane: str
    name: str                     # the HLO instruction's name
    module: Optional[str]         # its HLO module, where the trace says
    start_s: float                # trace clock
    dur_s: float
    control: bool                 # holds other ops (while, conditional, call)


@dataclass
class Trace:
    ops: List[Op]
    spans: List[HostSpan]         # the program's spans, on the trace clock
    mark: Optional[float]         # the window mark's instant, trace clock
    on_device: bool = True        # ops of device planes, not host events


def read(path: str) -> Trace:
    """The device ops, the program's host spans and the window mark of
    the trace at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: List[Op] = []
    host_ops: List[Op] = []
    spans: List[HostSpan] = []
    mark = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops += _device_ops(plane)
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == devtrace.WINDOW_MARK:
                    mark = e.start_ns * 1e-9 if mark is None else mark
                elif e.name == STEP_SPAN or e.name.startswith(SPAN_PREFIXES):
                    spans.append(HostSpan(e.name, (plane.name, i),
                                          e.start_ns * 1e-9, e.end_ns * 1e-9))
                else:
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        host_ops.append(Op(
                            plane.name, str(st["hlo_op"]),
                            str(st.get("hlo_module")), e.start_ns * 1e-9,
                            e.duration_ns * 1e-9, False))
    return Trace(ops=ops or host_ops, spans=spans, mark=mark,
                 on_device=bool(ops))


def _device_ops(plane) -> List[Op]:
    lines = {line.name: line for line in plane.lines}
    if devtrace.OPS_LINE not in lines:
        return []
    modules = sorted((e.start_ns, e.end_ns, _module_name(e.name))
                     for e in (lines[MODULES_LINE].events
                               if MODULES_LINE in lines else ()))
    starts = [m[0] for m in modules]
    out = []
    for e in lines[devtrace.OPS_LINE].events:
        module = None
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k >= 0 and e.start_ns < modules[k][1]:
            module = modules[k][2]
        elif not modules:
            module = dict(e.stats).get("hlo_module")
        out.append(Op(plane.name, devtrace.short_name(e.name),
                      None if module is None else str(module),
                      e.start_ns * 1e-9, e.duration_ns * 1e-9,
                      any(c in e.name for c in devtrace.CONTROL_FLOW)))
    return out


def _module_name(event_name: str) -> str:
    """``jit_train_step(12)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def program_spans(trace: Trace, t0: float) -> List[HostSpan]:
    """The program's host spans on the host clock, given that the window
    mark was written at host time ``t0``.  Empty where the trace holds no
    mark or the program wrote no span."""
    if trace.mark is None:
        return []
    shift = trace.mark - t0                       # trace clock - host clock
    return sorted((HostSpan(s.name, s.thread, s.t0 - shift, s.t1 - shift)
                   for s in trace.spans), key=lambda s: s.t0)


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> Optional[str]:
    """The innermost component of an ``op_name`` path that names a scope,
    looking inside transform wrappers (``transpose(jvp(loss))``)."""
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            return part
    return None


@dataclass
class Hlo:
    """What the scope split needs of one optimized HLO module."""
    module: str
    scopes: Dict[str, str]                        # instruction -> scope
    control: FrozenSet[str] = field(default_factory=frozenset)
    names: FrozenSet[str] = field(default_factory=frozenset)


def parse_hlo(hlo_text: str, scopes: Sequence[str] = SCOPES) -> Hlo:
    first = hlo_text.lstrip().split(None, 2)
    module = first[1].rstrip(",") if first[:1] == ["HloModule"] else ""
    control, names = set(), set()
    for line in hlo_text.splitlines():
        if " = " in line:
            lhs, rhs = line.split(" = ", 1)
            name = lhs.split()[-1].lstrip("%")
            names.add(name)
            op = _OPCODE.search(" " + rhs.split(", metadata=")[0])
            if op and op.group(1) in CONTROL_OPCODES:
                control.add(name)
    return Hlo(module, hlo_scopes(hlo_text, scopes), frozenset(control),
               frozenset(names))


def hlo_scopes(hlo_text: str, scopes: Sequence[str] = SCOPES) -> Dict[str, str]:
    """Instruction name -> scope, over an HLO module's text.

    An instruction takes the scope its ``op_name`` names.  One the
    compiler made, with no ``op_name`` at all (a layout copy, a convert
    hoisted out of a loop, the pieces of a rewritten cumsum), takes the
    scope of the instructions it reads, else of those that read it; a
    fusion with none takes first the scope most of its fused
    computation's instructions name."""
    comps: Dict[str, Dict[str, _Instr]] = {}
    current: Dict[str, _Instr] = {}
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
            current = comps.setdefault(head.lstrip("%"), {})
            continue
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        name = lhs.split()[-1].lstrip("%")
        op = _OP_NAME.search(rhs)
        calls = _CALLS.search(rhs)
        current[name] = _Instr(scope_of(op.group(1), scopes) if op else None,
                               op is not None or " parameter(" in rhs,
                               calls.group(1) if calls else None,
                               _REF.findall(rhs))
    for instrs in comps.values():
        for i in instrs.values():
            if i.scope is None and not i.named and i.calls in comps:
                inner = Counter(x.scope for x in comps[i.calls].values()
                                if x.scope)
                i.scope = inner.most_common(1)[0][0] if inner else None
        _by_dataflow(instrs)
    return {name: i.scope for instrs in comps.values()
            for name, i in instrs.items() if i.scope is not None}


@dataclass
class _Instr:
    scope: Optional[str]
    named: bool                   # has op_name metadata, or is an input
    calls: Optional[str]
    refs: List[str]


def _by_dataflow(instrs: Dict[str, _Instr]) -> None:
    """Gives each unnamed instruction the scope of its first scoped
    operand, else of its first scoped user, following chains."""
    users: Dict[str, List[str]] = {}
    for name, i in instrs.items():
        for r in i.refs:
            if r in instrs and r != name:
                users.setdefault(r, []).append(name)
    for neighbours in (lambda n, i: i.refs, lambda n, i: users.get(n, [])):
        changed = True
        while changed:
            changed = False
            for name, i in instrs.items():
                if i.named or i.scope is not None:
                    continue
                found = next((instrs[r].scope for r in neighbours(name, i)
                              if r in instrs and instrs[r].scope), None)
                if found:
                    i.scope, changed = found, True


Unions = Dict[str, Dict[Optional[str], List[Tuple[float, float]]]]


def scope_unions(trace: Trace, t0: float, t_end: float,
                 hlo: Hlo) -> Optional[Unions]:
    """Per device plane and scope, the union of the intervals in which
    the device ops of the window [t0, t_end] ran (host clock, ``t0`` the
    window mark's instant, as for ``devtrace.reduce``), on the trace
    clock.  Only ops of ``hlo``'s module take a scope; an op whose module
    the trace does not give is taken to be of it.  Ops that hold others
    (while, conditional, call) count only through the ops they run, and
    fall, with every op of no scope, under None.  None where the trace
    holds no device op or mark."""
    if trace.mark is None or not trace.ops:
        return None
    shift = trace.mark - t0
    lo, hi = t0 + shift, t_end + shift
    planes: Unions = {op.plane: {} for op in trace.ops}
    for op in trace.ops:
        a, b = max(op.start_s, lo), min(op.start_s + op.dur_s, hi)
        if b <= a:
            continue
        scope = None
        if not (op.module not in (None, hlo.module) or op.control
                or op.name in hlo.control):
            scope = hlo.scopes.get(op.name)
        planes[op.plane].setdefault(scope, []).append((a, b))
    return {p: {s: devtrace.union(iv) for s, iv in by.items()}
            for p, by in planes.items()}


def device_s(planes: Unions, scopes: Optional[Iterable[str]] = None) -> float:
    """Seconds in which a device op under one of ``scopes`` ran (any op
    where None), from ``scope_unions``, averaged over devices."""
    wanted = None if scopes is None else frozenset(scopes)
    total = 0.0
    for by_scope in planes.values():
        total += _length(devtrace.union(
            x for s, iv in by_scope.items()
            if wanted is None or s in wanted for x in iv))
    return total / len(planes)


def _length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)
