"""Device milliseconds per window step under the step's ``loss`` scope
(unembedding and chunked cross-entropy, with their backward): the union
of those ops' intervals in the trace."""

SCOPES = ("loss",)


def read(run):
    return run.scope_ms(SCOPES)
