"""Host milliseconds per window step in the profiler's ``on_step_begin``
and ``on_step_end``, its session restarts included."""


def read(run):
    if not run.profiled:
        return None
    steps = run.window_steps
    spans = run.spans.of("profiler", steps)
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(steps)
