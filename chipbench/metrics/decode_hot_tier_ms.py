"""Device milliseconds of one decode execution (``jit_decode``) in the
``hot_tier`` scope: the hot tier's residency bookkeeping (``swap_in``,
the warm-up and speculative inserts), averaged over the traced part's
executions."""


def read(run):
    if run.scope_ms is None:
        return None
    return run.scope_ms.get("hot_tier")
