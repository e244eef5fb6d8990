"""The trace reduction, on a small trace recorded on a TPU v5e (a reduced
serving run: a few engine steps, 0.6 s window; pruned to the device lines
and host spans the reduction reads, op names cut after ``=``) and on
synthetic intervals."""
from pathlib import Path

import pytest

from chipbench import trace

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_merge_and_clip():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8), (9, 10)], (2, 6)) == [(2, 3), (5, 6)]
    assert trace.module_name("jit_decode(123)") == "jit_decode"


@pytest.fixture(scope="module")
def small():
    return trace.Trace(str(SMALL))


def test_finds_window_device_and_programs(small):
    assert small.window is not None and small.n_devices == 1
    decode = small.executions("jit_decode")
    prefill = small.executions("jit_prefill")
    assert len(decode) >= 3 and len(prefill) >= 1
    w0, w1 = small.window
    assert all(w0 <= s < e for s, e in decode + prefill)


def test_busy_and_idle_add_up_to_the_window(small):
    busy = small.busy_s()
    assert 0 < busy < small.window_s()
    # operations run inside programs, so they cover no more of the window
    assert 0 < small.busy_s(ops=True) <= busy * 1.0001
    idle = small.idle_by_span(n=1000)
    assert sum(v for _, v in idle) == pytest.approx(
        small.window_s() - busy, rel=1e-6)
    assert all(k == "(no span)" or k.startswith(trace.SPAN_PREFIXES)
               for k, _ in idle)
    # device time inside the decode program can not exceed its executions
    dec = sum(e - s for s, e in small.executions("jit_decode")) / 1e9
    ops = sum(v for k, v in small.top_ops(n=100000)
              if k.startswith("jit_decode/"))
    assert 0 < ops <= dec * 1.0001


def test_top_ops_are_named_by_program(small):
    top = small.top_ops()
    assert 0 < len(top) <= 10
    assert all("/" in k and v > 0 for k, v in top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
