"""Device milliseconds per window step under the step's ``conv`` scope
(the depthwise causal convolution), forward, recompute and backward: the
union of those ops' intervals in the trace."""

SCOPES = ("conv",)


def read(run):
    return run.scope_ms(SCOPES)
