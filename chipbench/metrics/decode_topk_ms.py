"""Device milliseconds of one decode execution (``jit_decode``) in the
``topk`` scope: the selection of each step's top-k positions from the
indexer's scores, averaged over the traced part's executions."""


def read(run):
    if run.scope_ms is None:
        return None
    return run.scope_ms.get("topk")
