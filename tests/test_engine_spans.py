"""The engine's host spans, their ring, and its count of device reads
(reduced config, CPU)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import engine as engine_module
from repro.serving import prefetch, spans
from repro.serving.engine import Engine
from repro.serving.request import sharegpt_trace

ADMISSION = ("Engine.radix", "Engine.prefill", "Engine.splice",
             "Engine.warm")
PHASES = ("Engine.admit",) + ADMISSION + (
    "Engine.prepare", "Engine.decode", "Engine.wait_token",
    "Engine.counters", "Engine.account", "Engine.resize", "Engine.finish")


def _engine():
    cfg = get_config("qwen2-1.5b").reduced()
    # a resize every other step, so the LayerSizer block runs
    cfg = dataclasses.replace(
        cfg, sac=dataclasses.replace(cfg.sac, resize_interval=2))
    return Engine(cfg, slots=2, max_ctx=96, prefetch=True, seed=0)


def _requests(cfg, n=3):
    return sharegpt_trace(n, context_len=40, output_len=5, seed=3,
                          ctx_jitter=0.0, vocab=cfg.vocab)


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    reqs = _requests(eng.cfg)
    out = eng.run(reqs)
    assert out["n_done"] == len(reqs)
    return eng, reqs


def test_every_phase_nests_in_its_step(served):
    eng, _ = served
    log = eng.phase_log()
    assert len(log) == eng.stats.steps
    assert set(PHASES) <= {s.name for step in log for s in step}
    for n, step in enumerate(log):
        root = step[0]
        assert (root.name, root.parent, root.args) == (
            "Engine.step", None, {"step": n})
        assert all(s.name != "Engine.step" for s in step[1:])
        for s in step[1:]:
            assert root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
        parents = {s.name: s.parent for s in step[1:]}
        assert all(parents[a] == "Engine.admit" for a in ADMISSION
                   if a in parents)
        assert all(p == "Engine.step" for a, p in parents.items()
                   if a not in ADMISSION)
        seconds = spans.phase_seconds(step)
        assert sum(seconds[p] for p in seconds
                   if p not in ADMISSION) <= root.seconds


def test_admission_spans_name_the_request(served):
    eng, reqs = served
    log = eng.phase_log()
    for name in ADMISSION:
        got = sorted(s.args["request_id"] for step in log for s in step
                     if s.name == name)
        assert got == sorted(r.request_id for r in reqs), name
    tokens = {s.args["request_id"]: s.args["tokens"] for step in log
              for s in step if s.name == "Engine.prefill"}
    assert tokens == {r.request_id: r.context_len for r in reqs}


class _CountingNumpy:
    """numpy, with a count of the jax arrays ``asarray`` copies to the
    host."""

    def __init__(self):
        self.device_values = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **k):
        self.device_values += isinstance(x, jax.Array)
        return np.asarray(x, *a, **k)


def test_device_reads_count_every_copy_to_the_host(monkeypatch):
    """Every device value the engine (and its fetch planner) copies to the
    host while serving goes through ``Engine._read``."""
    eng = _engine()
    reqs = _requests(eng.cfg)
    counting = _CountingNumpy()
    monkeypatch.setattr(engine_module, "np", counting)
    monkeypatch.setattr(prefetch, "np", counting)
    eng.run(reqs)
    assert counting.device_values == eng.stats.device_reads
    # per decode step: cache lengths, the sampled tokens, four hot-tier
    # and two speculation counters; per admission: the warm-up's scores
    # and its count of inserts; per finished request: the hot tier's
    # fill-chunk and miss counters
    assert eng.stats.device_reads == 8 * eng.stats.steps + 3 * len(reqs)


def test_ring_keeps_the_last_steps(monkeypatch):
    monkeypatch.setattr(spans, "STEPS_KEPT", 4)
    eng = _engine()
    eng.run(_requests(eng.cfg))
    log = eng.phase_log()
    assert eng.stats.steps > 4 and len(log) == 4
    assert [step[0].args["step"] for step in log] == list(
        range(eng.stats.steps - 4, eng.stats.steps))


def test_span_records_on_error():
    log = spans.SpanLog()
    with pytest.raises(ValueError):
        with log.span("Engine.step", step=0):
            with log.span("Engine.prepare"):
                raise ValueError
    [step] = log.steps()
    assert [(s.name, s.parent) for s in step] == [
        ("Engine.step", None), ("Engine.prepare", "Engine.step")]
    with log.span("Engine.step", step=1):
        pass
    assert len(log.steps()) == 2 and np.isfinite(log.steps()[1][0].seconds)
