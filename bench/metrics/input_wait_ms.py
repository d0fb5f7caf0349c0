"""Host milliseconds per window step spent inside ``next(batches)``."""


def read(run):
    steps = run.window_steps
    spans = run.spans.of("input", steps)
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / len(steps)
