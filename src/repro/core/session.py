"""ProfileSession: start/stop profiling windows with in-situ extraction.

Mirrors the three tf-Darshan invocation modes (paper §III-A):
  * manual       — ``session.start()`` / ``session.stop()`` around any code,
  * automatic    — ``StepCallback`` profiles a [start, stop] step range from
                   the trainer (the TensorBoard-callback batch window),
  * interactive  — ``ProfileServer`` accepts start/stop over a local socket
                   (the tf.profiler.server analogue).

``start()`` performs the runtime attachment if needed (no preload), takes a
snapshot of the Darshan module buffers; ``stop()`` takes the second
snapshot, computes the delta and runs the in-situ analysis — the paper's
key operational difference vs vanilla Darshan, which can only analyze
after process exit (Table I).
"""
from __future__ import annotations

import json
import socket
import sys
import threading
import time
from contextlib import nullcontext
from typing import Optional

from repro.core.attach import attach as _attach, detach as _detach, is_attached as _is_attached
from repro.core.analysis import SessionReport, analyze
from repro.core.records import delta
from repro.core.runtime import DarshanRuntime, get_runtime
from repro.link import (LINK_VERSION, Endpoint, LineServer, Message,
                        check_hello)
# Line framing lives in repro.link now (TcpTransport subsumed the old
# socket plumbing); re-exported here for the long-standing import path.
from repro.link.transport import (MAX_LINE_BYTES, recv_lines,  # noqa: F401
                                  recv_reply)


class ProfileSession:
    """``insight`` closes the paper's runtime-optimization loop: pass
    True (owned engine) or an ``InsightEngine`` and the session attaches
    it to the runtime hook on start() and polls it on a background
    thread every ``insight_interval_s`` (rolling windows keep the
    bounded event bus drained and give history-based detectors their
    trend); stop() runs a final poll and carries the findings raised
    during this window on the report (exported by to_chrome_trace /
    to_json_report, consumed by the advisors)."""

    def __init__(self, runtime: Optional[DarshanRuntime] = None,
                 auto_attach: bool = True, trace: bool = True,
                 insight=False, insight_interval_s: float = 0.5):
        self.rt = runtime or get_runtime()
        self.auto_attach = auto_attach
        self.rt.dxt.enabled = trace
        self._start_snap = None
        self._t0 = None
        self._active = False
        self.reports: list[SessionReport] = []
        self._detach_on_stop = False
        self.insight_interval_s = insight_interval_s
        self.insight_engine = None
        if insight:
            if insight is True:
                from repro.insight.engine import InsightEngine
                self.insight_engine = InsightEngine()
            else:
                self.insight_engine = insight

    # ------------------------------------------------------------- manual
    def start(self) -> None:
        if self._active:
            return
        if self.auto_attach and not _is_attached():
            _attach(self.rt)
            self._detach_on_stop = True
        if self.insight_engine is not None:
            self.insight_engine.attach(self.rt)
            self.insight_engine.start(self.insight_interval_s)
            self._insight_dropped_mark = getattr(
                self.insight_engine, "dropped_events",
                self.insight_engine.bus.dropped)
        # Nested sessions share the runtime (e.g. a fleet RankReporter
        # spanning the run with a StepCallback window inside): stop()
        # restores rather than clears, so the inner window's end doesn't
        # blind the outer one.
        self._enabled_before = self.rt.enabled
        self.rt.enabled = True
        self._listener_errors_mark = dict(self.rt.listener_errors)
        # self-telemetry window mark: stop() attaches the registry's
        # delta over this window as report.metrics (repro.obs)
        reg = getattr(self.rt, "metrics", None)
        self._metrics_mark = reg.snapshot() if reg is not None else None
        self._start_snap = self.rt.snapshot()
        self._t0 = self._start_snap["time"]
        self._active = True

    def stop(self) -> SessionReport:
        if not self._active:
            raise RuntimeError("session not started")
        stop_snap = self.rt.snapshot()
        self.rt.enabled = getattr(self, "_enabled_before", False)
        if self.insight_engine is not None:
            self.insight_engine.poll()           # flush the final window
            self.insight_engine.detach()
        if self._detach_on_stop:
            _detach()
            self._detach_on_stop = False
        self._active = False
        d_posix = delta(stop_snap["POSIX"], self._start_snap["POSIX"])
        d_stdio = delta(stop_snap["STDIO"], self._start_snap["STDIO"])
        cols = self.rt.trace.window(self._t0, stop_snap["time"])
        report = analyze(d_posix, d_stdio,
                         elapsed_s=stop_snap["time"] - self._t0,
                         dxt_segments=len(cols))
        report.segments_columns = cols  # rows derive lazily on access
        mark = getattr(self, "_listener_errors_mark", {})
        report.listener_errors = {
            k: v - mark.get(k, 0)
            for k, v in self.rt.listener_errors.items()
            if v - mark.get(k, 0) > 0}
        reg = getattr(self.rt, "metrics", None)
        report.metrics = (
            reg.delta(getattr(self, "_metrics_mark", None))
            if reg is not None else {})
        if self.insight_engine is not None:
            # Only findings active within this window: the owned engine
            # persists across session restarts (StepCallback's every=N
            # mode) and must not re-report earlier windows' findings.
            report.findings = [f for f in self.insight_engine.findings
                               if f.window[1] >= self._t0]
            report.insight_dropped_events = (
                getattr(self.insight_engine, "dropped_events",
                        self.insight_engine.bus.dropped)
                - getattr(self, "_insight_dropped_mark", 0))
        self.reports.append(report)
        return report

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        if self._active:
            self.stop()
        return False


class StepCallback:
    """Automatic profiling over a step window (TensorBoard-callback mode).

    Wire into a training loop:  cb.on_step_begin(i) / cb.on_step_end(i).
    Profiles steps in [first, last] inclusive; optionally restarts the
    session every ``every`` steps (the paper's STREAM validation restarts
    every 5 batches to derive a bandwidth series)."""

    def __init__(self, first: int, last: int, every: Optional[int] = None,
                 runtime: Optional[DarshanRuntime] = None,
                 session: Optional[ProfileSession] = None):
        self.first, self.last, self.every = first, last, every
        # ``session`` lets the repro.profiler façade drive a fully
        # configured ProfileSession (insight detectors, trace flag)
        # through the automatic step-window mode.
        self.session = session or ProfileSession(runtime)
        self.reports = self.session.reports

    def on_step_begin(self, step: int) -> None:
        if step == self.first:
            self._start()
        elif (self.every and self.first < step <= self.last
              and (step - self.first) % self.every == 0):
            self._stop()
            self._start()

    def on_step_end(self, step: int) -> None:
        if step == self.last and self.session._active:
            self._stop()

    def _start(self) -> None:
        with _trace_span("profiler.start"):
            self.session.start()

    def _stop(self) -> None:
        with _trace_span("profiler.stop"):
            self.session.stop()


def _trace_span(name: str):
    """A ``jax.profiler.TraceAnnotation`` where JAX is loaded, so the
    session's own stops and starts show on a training step's trace; a
    process that never imported JAX is not tracing with it."""
    jax = sys.modules.get("jax")
    return jax.profiler.TraceAnnotation(name) if jax else nullcontext()


class ProfileServer:
    """Interactive mode: line-oriented local TCP control, mirroring
    tf.profiler.server.start().

    Dual-stack on one port (a ``repro.link.LineServer``):

      * legacy text verbs — ``start`` / ``stop`` / ``status`` (the
        original single-rank protocol), plus the fleet extension:
        ``report`` (the last stopped window as a versioned wire payload
        a FleetCollector can ingest), ``findings`` (insight findings of
        the last window as JSON), and ``clock <t_send>`` (clock-
        handshake probe);
      * typed ``repro.link`` messages — any line starting with ``{`` is
        decoded and dispatched through the server's ``Endpoint``
        (kinds ``hello``/``start``/``stop``/``status``/``findings``/
        ``clock``/``report`` built in, ``register_verb`` extensions
        resolved from the registry), so a ``TcpTransport`` client and a
        netcat user drive the same session.

    Connections are read line-by-line, so one client may pipeline many
    commands.  ``idle_timeout_s`` bounds how long an idle connection's
    reader blocks between commands (plumbed from
    ``ProfilerOptions.idle_timeout_s`` by the façade)."""

    def __init__(self, port: int = 0, runtime: Optional[DarshanRuntime] = None,
                 rank: int = 0, nprocs: int = 1, insight=False,
                 idle_timeout_s: float = 2.0):
        self.session = ProfileSession(runtime, insight=insight)
        self.rank = rank
        self.nprocs = nprocs
        self._cmd_lock = threading.Lock()   # serialize session mutation
        self.endpoint = Endpoint(context=self, handlers={
            "hello": ProfileServer._msg_hello,
            "start": ProfileServer._msg_start,
            "stop": ProfileServer._msg_stop,
            "status": ProfileServer._msg_status,
            "findings": ProfileServer._msg_findings,
            "clock": ProfileServer._msg_clock,
            "report": ProfileServer._msg_report,
        })
        self._server = LineServer(self._dispatch, port=port, backlog=4,
                                  idle_timeout_s=idle_timeout_s)
        self.port = self._server.port

    # ---------------------------------------------------------- dispatch
    def _dispatch(self, line: str) -> Optional[str]:
        cmd = line.strip()
        with self._cmd_lock:
            if cmd.startswith("{"):
                return self.endpoint.dispatch_line(cmd)
            return self._dispatch_text(cmd)

    def _dispatch_text(self, cmd: str) -> str:
        verb, _, arg = cmd.partition(" ")
        if verb == "start":
            self.session.start()
            return "ok"
        if verb == "stop":
            try:
                return json.dumps(self._stop_dict())
            except RuntimeError as e:
                return f"error: {e}"
        if verb == "status":
            return f"active={self.session._active}"
        if verb == "findings":
            return json.dumps({"findings": self._last_findings()})
        if verb == "clock":
            reply = {"t": self.session.rt.now(), "wall": time.time()}
            if arg:
                try:
                    reply["echo"] = float(arg)
                except ValueError:
                    return "error: clock argument must be a number"
            return json.dumps(reply)
        if verb == "report":
            try:
                return self._report_line()
            except RuntimeError as e:
                return f"error: {e}"
        return "unknown"

    # ------------------------------------------------------- typed verbs
    # Handlers follow the Endpoint contract handler(endpoint, msg); the
    # server reaches itself through endpoint.context, so registry-wide
    # verb extensions see the same surface as these built-ins.
    @staticmethod
    def _msg_hello(endpoint, msg: Message) -> Message:
        srv = endpoint.context
        check_hello(msg.payload, side="client")
        return msg.reply("hello", {"link_v": LINK_VERSION,
                                   "rank": srv.rank,
                                   "nprocs": srv.nprocs,
                                   "caps": ["segments_columns"]})

    @staticmethod
    def _msg_start(endpoint, msg: Message) -> Message:
        endpoint.context.session.start()
        return msg.reply("ok")

    @staticmethod
    def _msg_stop(endpoint, msg: Message) -> Message:
        srv = endpoint.context
        try:
            return msg.reply("ok", srv._stop_dict())
        except RuntimeError as e:
            return msg.reply("error", {"error": str(e)})

    @staticmethod
    def _msg_status(endpoint, msg: Message) -> Message:
        return msg.reply("ok", {"active": endpoint.context.session._active})

    @staticmethod
    def _msg_findings(endpoint, msg: Message) -> Message:
        return msg.reply("ok",
                         {"findings": endpoint.context._last_findings()})

    @staticmethod
    def _msg_clock(endpoint, msg: Message) -> Message:
        srv = endpoint.context
        # clock_reply mirrors the collector's handshake shape (t_coll),
        # so a collector can pull-align against a ProfileServer too.
        payload = {"t_coll": srv.session.rt.now(), "wall": time.time()}
        if "t_send" in msg.payload:
            payload["echo"] = msg.payload["t_send"]
        return msg.reply("clock_reply", payload)

    @staticmethod
    def _msg_report(endpoint, msg: Message):
        srv = endpoint.context
        try:
            return srv._report_line()     # already an encoded report line
        except RuntimeError as e:
            return msg.reply("error", {"error": str(e)})

    # ------------------------------------------------------- shared ops
    def _stop_dict(self) -> dict:
        rep = self.session.stop()         # raises RuntimeError if idle
        return {
            "posix_bandwidth_mb_s": rep.posix_bandwidth_mb_s,
            "reads": rep.posix.reads,
            "bytes_read": rep.posix.bytes_read,
            "findings": [f.to_dict() for f in rep.findings],
        }

    def _last_findings(self) -> list:
        rep = self.session.reports[-1] if self.session.reports else None
        return [f.to_dict() for f in rep.findings] if rep else []

    def _report_line(self) -> str:
        if not self.session.reports:
            raise RuntimeError("no report")
        from repro.fleet.payloads import encode_report   # lazy: avoids cycle
        return encode_report(self.rank, self.session.reports[-1],
                             nprocs=self.nprocs)

    def close(self) -> None:
        # LineServer.close() joins handler threads: a handler still
        # holding a connection after close() would keep the old session
        # mutable while a successor server on the same port serves new
        # clients.
        self._server.close()
        # A window left open by a client must not leak the global
        # attach: later sessions would silently record into THIS
        # server's runtime instead of their own.
        if self.session._active:
            try:
                self.session.stop()
            except RuntimeError:
                pass


class ProfileServerError(RuntimeError):
    """A ProfileServer control exchange failed: the server replied with
    an error/unknown-verb line, or the reply wasn't the JSON the caller
    asked to parse."""


def control(port: int, cmd: str, parse: bool = False):
    """Client helper for ProfileServer.  Returns the raw reply string,
    or the decoded JSON object when ``parse=True`` (e.g. the ``stop``
    reply with its ``findings`` list).

    With ``parse=True``, an error/``unknown`` reply or a malformed
    (non-JSON) reply raises ``ProfileServerError`` naming the verb and
    the offending reply, instead of surfacing a raw JSONDecodeError."""
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(cmd.encode() + b"\n")
        reply = recv_reply(s)
    if parse:
        if reply.startswith(("error", "ERR")) or reply == "unknown":
            raise ProfileServerError(
                f"server rejected {cmd.partition(' ')[0]!r}: {reply}")
        try:
            return json.loads(reply)
        except json.JSONDecodeError as e:
            raise ProfileServerError(
                f"malformed reply to {cmd.partition(' ')[0]!r}: {reply!r}") from e
    return reply
