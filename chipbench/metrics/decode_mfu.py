"""The whole decode step's share of the chip's peak bf16 FLOP/s: required
FLOPs per step (averaged over the window's steps) over the measured
device time of one decode execution times the peak."""


def read(run):
    if run.trace is None or not run.step_work:
        return None
    ex = run.trace.executions("jit_decode")
    if not ex:
        return None
    flops = sum(w["flops"] for w in run.step_work) / len(run.step_work)
    per_step = sum(e - s for s, e in ex) / 1e9 / len(ex)
    return 100.0 * flops / (per_step * run.peaks["bf16_flops_per_s"])
