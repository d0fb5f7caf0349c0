"""Device busy time (the union of the device's operation intervals in
the trace) over the window, per window step."""


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace.busy_s / len(run.window_steps)
