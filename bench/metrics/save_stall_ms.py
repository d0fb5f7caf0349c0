"""Host milliseconds per save that the step thread spends in
``save_async`` once the saved step's outputs are ready: the wait for the
previous save, if it is still writing, and the ``device_get`` snapshot.
Before that moment the device is still working through the steps queued
ahead of the save, so that part costs it nothing and is not counted."""


def read(run):
    spans = [s for s in run.spans.of("save_stall") if s.step >= run.warmup]
    if not spans:
        return None
    return 1e3 * sum(s.t1 - max(s.t0, run.state_ready(s.step))
                     for s in spans) / len(spans)
