"""Device milliseconds of the prefill program (``jit_prefill``) per 1000
prompt tokens prefilled in the window."""


def read(run):
    if run.trace is None:
        return None
    ex = run.trace.executions("jit_prefill")
    tokens = sum(n for t, n in run.prefills if run.t0 <= t < run.t1)
    if not ex or not tokens:
        return None
    return sum(e - s for s, e in ex) / 1e6 / (tokens / 1000)
