"""Device milliseconds per window step under the step's ``in_proj`` or
``out_proj`` scope (the block's input projection; its gate, norm and
output projection), forward, recompute and backward: the union of those
ops' intervals in the trace."""

SCOPES = ("in_proj", "out_proj")


def read(run):
    return run.scope_ms(SCOPES)
