"""Device milliseconds of one decode execution (``jit_decode``) in the
expert layers' scopes, ``router`` + ``experts`` + ``shared_expert``,
averaged over the traced part's executions.  None where the decode has
no such scope."""

SCOPES = ("router", "experts", "shared_expert")


def read(run):
    if run.scope_ms is None or not any(s in run.scope_ms for s in SCOPES):
        return None
    return sum(run.scope_ms.get(s, 0.0) for s in SCOPES)
