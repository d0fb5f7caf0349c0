"""Seconds per window save in the writer's ``ckpt.fsync`` spans (the
``fsync`` of each file it writes), from the trace."""


def read(run):
    return run.per_save_s("ckpt.fsync")
