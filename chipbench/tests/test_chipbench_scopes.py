"""Named scopes and program spans: the scope map on a hand-written module,
and the readings on a small trace recorded on a TPU v5e (a reduced serving
run of the program with its spans and scopes, a few engine steps; pruned
like ``small.xplane.pb``) with the text of the decode program that run
compiled."""
import bisect
import gzip
import importlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import scopes, trace
from chipbench.metrics import host_step_ms

DATA = Path(__file__).resolve().parent / "data"
# the scopes of the program that recorded the trace, as its configuration
# file lists them
NAMES = json.loads((Path(__file__).resolve().parents[1] / "configs"
                    / "qwen2-1.5b.json").read_text())["scopes"]
SCOPE_READERS = {"decode_hot_tier_ms": "hot_tier", "decode_topk_ms": "topk",
                 "decode_indexer_ms": "indexer",
                 "decode_pool_write_ms": "pool_write"}

HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %tanh.0 = f32[4]{0} tanh(%param_0), metadata={op_name="jit(f)/mlp/tanh"}
}

ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %sine.1 = f32[4]{0} sine(%x.1), metadata={op_name="jit(f)/indexer/sin"}
  %copy.1 = f32[4]{0} copy(%sine.1)
  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %copy.2 = f32[4]{0} copy(%fusion.1)
  %cosine.1 = f32[4]{0} cosine(%copy.2), metadata={op_name="jit(f)/gather"}
  %negate.1 = f32[4]{0} negate(%cosine.1), metadata={op_name="jit(f)/gather/gather/neg"}
  ROOT %exp.1 = f32[4]{0} exponential(%negate.1), metadata={op_name="jit(f)/moe/experts/exp"}
}
"""


def test_scope_of_is_the_innermost_listed_scope_before_the_primitive():
    assert scopes.scope_of("jit(decode)/while/body/closed_call/hot_tier/"
                           "jit(take_along_axis)/gather", NAMES) == "hot_tier"
    assert scopes.scope_of("jit(decode)/layers/while/body/closed_call/"
                           "topk/sort", NAMES) == "topk"
    assert scopes.scope_of("jit(decode)/gather", NAMES) == scopes.UNSCOPED
    assert scopes.scope_of("", NAMES) == scopes.UNSCOPED


def test_scopes_come_from_the_argument():
    """A name is a scope only where the list given names it: ``moe`` and
    ``experts`` are no scopes of the qwen2 program's list."""
    path = "jit(decode)/layers/moe/experts/dot_general"
    assert scopes.scope_of(path, NAMES) == "layers"
    assert scopes.scope_of(path, ["moe"]) == "moe"
    assert scopes.scope_of(path, ["moe", "experts"]) == "experts"
    assert scopes.op_scopes(HLO, NAMES)["%exp.1"] == scopes.UNSCOPED
    assert scopes.op_scopes(HLO, [*NAMES, "moe"])["%exp.1"] == "moe"
    assert scopes.op_scopes(HLO, ["moe"])["%sine.1"] == scopes.UNSCOPED


def test_op_scopes_from_paths_fusions_and_users():
    got = scopes.op_scopes(HLO, NAMES)
    assert got["%sine.1"] == "indexer"
    assert got["%fusion.1"] == "mlp"          # its fused computation's root
    assert got["%copy.1"] == "mlp"            # no path: the scope of its user
    assert got["%copy.2"] == "mlp"            # no scoped user: its operand's
    assert got["%cosine.1"] == scopes.UNSCOPED   # ran outside every scope
    assert got["%negate.1"] == "gather"
    assert got["%x.1"] == scopes.UNSCOPED


@pytest.fixture(scope="module")
def scoped():
    return trace.Trace(str(DATA / "scoped.xplane.pb"))


@pytest.fixture(scope="module")
def op_scope():
    with gzip.open(DATA / "scoped_decode.hlo.gz", "rt") as f:
        return scopes.op_scopes(f.read(), NAMES)


def _decode_ops(tr):
    w0, w1 = tr.window
    return [(op, s, e, own) for (mod, op, s, e), own in zip(tr.ops,
                                                            tr.self_s)
            if mod == "jit_decode" and w0 <= s < w1]


def test_scopes_add_up_to_the_decode_op_time(scoped, op_scope):
    ops = _decode_ops(scoped)
    assert ops and all(op in op_scope for op, _, _, _ in ops)
    by = scopes.scope_s(scoped, "jit_decode", op_scope, NAMES)
    assert set(by) == set(NAMES) | {scopes.UNSCOPED}
    assert sum(by.values()) == pytest.approx(sum(o for *_, o in ops))
    # self times tile the time in which a decode op ran
    busy = sum(e - s for s, e in trace.merge([(s, e) for _, s, e, _ in ops]))
    assert sum(by.values()) == pytest.approx(busy / 1e9, rel=0.01)
    for part in ("indexer", "topk", "gather", "hot_tier", "attention",
                 "pool_write", "mlp", "lm_head", "layers"):
        assert by[part] > 0, part
    assert by[scopes.UNSCOPED] <= 0.1 * sum(by.values())


def test_scope_ms_is_the_decode_op_time_per_execution(scoped, op_scope):
    by, unmapped = scopes.scope_ms(scoped, "jit_decode", op_scope, NAMES)
    assert unmapped == 0
    assert set(by) == set(NAMES) | {scopes.UNSCOPED}
    ops = _decode_ops(scoped)
    busy = sum(e - s for s, e in trace.merge([(s, e) for _, s, e, _ in ops]))
    n = len(scoped.executions("jit_decode"))
    assert sum(by.values()) == pytest.approx(busy / 1e6 / n, rel=1e-3)
    assert all(v > 0 for k, v in by.items() if k != "pool_slice")


def test_ops_unmapped_counts_ops_absent_from_the_text(scoped, op_scope):
    """An op the text does not name (the text is of another program)
    is counted, and the split is then None."""
    ops = _decode_ops(scoped)
    gone = max(ops, key=lambda o: o[3])[0]
    partial = {k: v for k, v in op_scope.items() if k != gone}
    by, unmapped = scopes.scope_ms(scoped, "jit_decode", partial, NAMES)
    assert by is None
    assert unmapped == sum(op == gone for op, *_ in ops) > 0


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_readers_read_their_scope(name):
    reader = importlib.import_module(f"chipbench.metrics.{name}")
    assert reader.read(SimpleNamespace(scope_ms=None)) is None
    split = {s: float(i) + 0.5 for i, s in enumerate(NAMES)}
    assert reader.read(SimpleNamespace(scope_ms=split)) == split[
        SCOPE_READERS[name]]


def _spans(tr, name):
    return sorted((s, e) for n, s, e in tr.spans if n == name)


def test_each_decode_runs_between_its_dispatch_and_its_token(scoped):
    """The program's spans and the device's ops share the trace's clock:
    each decode execution starts while its step's ``Engine.decode`` is
    open and ends before that step's ``Engine.wait_token`` closes.  The
    profiler aligns the device's timestamps to the host's to within about
    0.2 ms: in this trace an execution appears up to 184 us before the
    span that dispatched it opened."""
    slack = 250_000                                    # ns
    steps = _spans(scoped, "Engine.step")
    dispatch = _spans(scoped, "Engine.decode")
    wait = _spans(scoped, "Engine.wait_token")
    ex = scoped.executions("jit_decode")
    assert len(ex) >= 3
    starts = [s for s, _ in steps]
    for s, e in ex:
        step = steps[bisect.bisect_right(starts, s) - 1]
        assert step[0] <= s <= step[1]
        [d] = [iv for iv in dispatch if step[0] <= iv[0] <= step[1]]
        [w] = [iv for iv in wait if step[0] <= iv[0] <= step[1]]
        assert d[0] - slack <= s <= d[1] and e <= w[1]


def test_host_step_ms_reads_the_program_spans(scoped):
    got = host_step_ms.read(SimpleNamespace(trace=scoped))
    w0, w1 = scoped.window
    step = [e - s for s, e in _spans(scoped, "Engine.step")
            if w0 <= s and e <= w1]
    assert 0 < got < max(step) / 1e6
    # a program without the spans (the one small.xplane.pb traced) reads
    # none
    old = trace.Trace(str(DATA / "small.xplane.pb"))
    assert host_step_ms.read(SimpleNamespace(trace=old)) is None


def test_decode_text_is_of_the_executable_that_ran():
    """The harness takes the decode's text from the jitted function the
    window called, on the engine's state and the last call's other
    arguments: JAX's caches hand back the executable that ran, with no
    compile of its own, and its scopes are the program's."""
    import jax
    import jax.numpy as jnp
    run = importlib.import_module("chipbench.run")

    def decode(params, state, tokens):
        with jax.named_scope("topk"):
            new = jnp.sort(state + params * tokens[:, None], axis=-1)
        with jax.named_scope("mlp"):
            return new, jnp.tanh(new).sum(-1)

    jitted = jax.jit(decode)
    eng = SimpleNamespace(params=jnp.float32(2.0),
                          state=jnp.arange(12.0).reshape(3, 4))
    win = SimpleNamespace(decode=jitted, decode_rest=None)
    log = run.CompileLog()
    assert run.decode_text(eng, win, log)["text"] == ""
    tokens = jnp.array([1.0, 2.0, 3.0])
    for _ in range(2):
        win.decode_rest = (tokens,)
        eng.state, _ = jitted(eng.params, eng.state, tokens)
    got = run.decode_text(eng, win, log)
    assert got["compiles"] == 0
    assert got["text"] == jitted.lower(eng.params, eng.state,
                                       tokens).compile().as_text()
    assert {"topk", "mlp"} <= set(scopes.op_scopes(got["text"],
                                                   ["topk", "mlp"]).values())
