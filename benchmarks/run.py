"""Benchmark harness: one module per paper table/figure.  The figures are
simulator outputs for the paper's GPU+CXL testbed, not chip measurements.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig10,fig13]

Prints each figure's reproduction against the paper's numbers, then a
``name,us_per_call,derived`` CSV block.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced traces (CI-speed)")
    ap.add_argument("--only", default="",
                    help="comma list: fig5,fig9,fig10,fig11,fig12,fig13,"
                         "fig14,prefetch")
    args = ap.parse_args()

    from benchmarks import (appendix_d, fig5_retrieval, fig9_round1,
                            fig10_round2, fig11_scalability, fig12_nondisagg,
                            fig13_interleave, fig14_buffer, prefetch_sweep)
    from benchmarks.common import Csv

    mods = {
        "fig5": fig5_retrieval, "fig9": fig9_round1, "fig10": fig10_round2,
        "fig11": fig11_scalability, "fig12": fig12_nondisagg,
        "fig13": fig13_interleave, "fig14": fig14_buffer,
        "prefetch": prefetch_sweep,
        "appendixD": appendix_d,
    }
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    csv = Csv()
    t0 = time.time()
    for name, mod in mods.items():
        if only and name not in only:
            continue
        mod.run(csv=csv, quick=args.quick)
    print(f"\n[benchmarks] total {time.time()-t0:.0f}s\n")
    csv.dump()


if __name__ == "__main__":
    main()
