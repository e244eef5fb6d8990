"""Shared benchmark plumbing: trace construction, backend sweep, CSV rows."""
from __future__ import annotations

from typing import Dict, List

from repro.configs import get_config
from repro.serving.request import sharegpt_trace
from repro.serving.simulator import (SimConfig, default_backends,
                                     profile_from_config, simulate)

CTXS = (16384, 32768, 65536, 131072)
PAPER_MODEL = "deepseek-v32"


def model_profile(arch: str = PAPER_MODEL):
    return profile_from_config(get_config(arch))


def run_cell(backend_name: str, *, ctx: int, concurrency: int = 64,
             n_requests: int = 512, output_len: int = 1024,
             device_buffer: int = 6144, round1: bool = False,
             backends=None, arch: str = PAPER_MODEL, seed: int = 1,
             n_pool_devices: int = None, **sim_kw) -> Dict[str, float]:
    """``sim_kw`` passes through to SimConfig (e.g. the fetch-pipeline
    knobs ``prefetch_width`` / ``overlap_frac`` / ``pipeline_depth``)."""
    import dataclasses
    backends = backends or default_backends()
    b = backends[backend_name]
    if n_pool_devices is not None:
        b = dataclasses.replace(b, n_pool_devices=n_pool_devices,
                                interleave=n_pool_devices > 1)
    reqs = sharegpt_trace(n_requests, context_len=ctx,
                          output_len=output_len, seed=seed)
    return simulate(reqs, model_profile(arch), b,
                    SimConfig(concurrency=concurrency,
                              device_buffer=device_buffer, round1=round1,
                              **sim_kw))


class Csv:
    """Collect ``name,us_per_call,derived`` rows (the run.py contract)."""

    def __init__(self):
        self.rows: List[str] = []

    def add(self, name: str, us_per_call: float, derived: str = ""):
        self.rows.append(f"{name},{us_per_call:.3f},{derived}")

    def dump(self):
        print("name,us_per_call,derived")
        for r in self.rows:
            print(r)
