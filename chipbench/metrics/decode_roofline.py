"""Share of the roofline reached by the decode step: the least time the
chip could take for a step's required work (the larger of its FLOPs over
peak FLOP/s and its bytes over peak bandwidth), averaged over the
window's steps, over the measured device time of one decode execution."""


def read(run):
    if run.trace is None or not run.step_work:
        return None
    ex = run.trace.executions("jit_decode")
    if not ex:
        return None
    p = run.peaks
    least = [max(w["flops"] / p["bf16_flops_per_s"],
                 w["bytes"] / p["hbm_bytes_per_s"]) for w in run.step_work]
    per_step = sum(e - s for s, e in ex) / 1e9 / len(ex)
    return 100.0 * (sum(least) / len(least)) / per_step
