"""Bytes of the window's saves (the state's leaves) over the writer
thread's time in ``CheckpointManager.save``, in MB (1e6 bytes) per
second."""


def read(run):
    saves = {r["step"]: r["bytes"] for r in run.saves if r["step"] >= run.warmup}
    spans = [s for s in run.spans.of("save_write") if s.step in saves]
    if not spans:
        return None
    return (sum(saves[s.step] for s in spans) / 1e6
            / sum(s.t1 - s.t0 for s in spans))
