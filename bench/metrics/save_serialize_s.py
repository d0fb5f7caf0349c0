"""Seconds per window save in the writer's ``ckpt.serialize`` spans (per
leaf: host copy, ``.npy`` bytes, crc32), from the trace."""


def read(run):
    return run.per_save_s("ckpt.serialize")
