"""Device milliseconds of one execution of the decode program
(``jit_decode``), averaged over the window's executions."""


def read(run):
    if run.trace is None:
        return None
    ex = run.trace.executions("jit_decode")
    if not ex:
        return None
    return sum(e - s for s, e in ex) / 1e6 / len(ex)
