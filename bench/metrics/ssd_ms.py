"""Device milliseconds per window step under the step's ``ssd`` scope
(the chunked state-space scan, ``ssd_chunked``), forward, recompute and
backward: the union of those ops' intervals in the trace."""

SCOPES = ("ssd",)


def read(run):
    return run.scope_ms(SCOPES)
