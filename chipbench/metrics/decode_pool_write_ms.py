"""Device milliseconds of one decode execution (``jit_decode``) in the
``pool_write`` scope: the write of each step's new KV entry and indexer
key into the pool, averaged over the traced part's executions."""


def read(run):
    if run.scope_ms is None:
        return None
    return run.scope_ms.get("pool_write")
