"""Share of the roofline reached by the expert layers of the decode step:
the least time the chip could take for their required work (the larger of
``moe_flops`` over peak FLOP/s and ``moe_bytes`` over peak bandwidth, from
``chipbench/work/mla_moe.py``), averaged over the window's steps, over the
device time of their scopes per decode execution (``dsv32_moe_ms``)."""
from chipbench.metrics import dsv32_moe_ms


def read(run):
    ms = dsv32_moe_ms.read(run)
    work = [w for w in (run.step_work or []) if "moe_flops" in w]
    if not ms or not work:
        return None
    p = run.peaks
    least = [max(w["moe_flops"] / p["bf16_flops_per_s"],
                 w["moe_bytes"] / p["hbm_bytes_per_s"]) for w in work]
    return 100.0 * (sum(least) / len(least)) / (ms / 1e3)
