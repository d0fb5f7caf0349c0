"""``device_step_ms`` less the union of the scopes that ``ssd_ms``,
``proj_ms``, ``loss_ms``, ``optimizer_ms`` and ``conv_ms`` read: device
milliseconds per window step under no scope of the step (the layer
scan's carry stores, the embedding, compiler-made ops it cannot place)
or in another program (the save's checksum and snapshot)."""

SCOPES = ("ssd", "in_proj", "out_proj", "loss", "optimizer", "conv")


def read(run):
    scoped = run.scope_ms(SCOPES)
    if run.trace is None or scoped is None:
        return None
    return 1e3 * run.trace.busy_s / len(run.window_steps) - scoped
