"""Device milliseconds per window step under the step's ``optimizer``
scope (gradient clipping and AdamW): the union of those ops' intervals
in the trace."""

SCOPES = ("optimizer",)


def read(run):
    return run.scope_ms(SCOPES)
