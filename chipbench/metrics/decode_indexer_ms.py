"""Device milliseconds of one decode execution (``jit_decode``) in the
``indexer`` scope: the lightning indexer's scores over each slot's
context, averaged over the traced part's executions."""


def read(run):
    if run.scope_ms is None:
        return None
    return run.scope_ms.get("indexer")
