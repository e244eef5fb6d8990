"""The named scopes of the decode program label its ops and change none of
them (reduced config, lowered on the CPU)."""
import contextlib
import re

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.model import build_model

SCOPES = ("indexer", "topk", "gather", "hot_tier", "attention", "pool_slice",
          "pool_write", "mlp", "lm_head", "layers")


def _lower(seq_len: int = 64):
    """The reduced decode, built as ``Engine(..., prefetch=True)`` builds
    it (a fresh model each call, so nothing is reused from a trace made
    under other scopes), over 2 slots of ``seq_len`` positions."""
    cfg = get_config("qwen2-1.5b").reduced()
    sac = cfg.sac
    model = build_model(cfg, mode="sac", opts={
        "prefetch_width": sac.prefetch_width,
        "score_margin": sac.score_margin, "warmup_w": sac.warmup_entries})
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = model.serve_state_shapes(2, seq_len,
                                     device_buffer=sac.device_buffer_size)
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32)
    return jax.jit(model.decode).lower(params, state, tokens)


def _program(compiled: str) -> str:
    """The compiled module's computations with their metadata taken out
    (the header tables of source files and stack frames go with it)."""
    body = compiled[compiled.index("\n%"):]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


def _scopes(lowered) -> set:
    """The scopes on the op name paths of the lowered module (the last
    part of a path names the primitive)."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")[:-1]}


def test_every_scope_labels_decode_ops_and_changes_no_op(monkeypatch):
    scoped = _lower()
    assert set(SCOPES) <= _scopes(scoped)
    compiled = scoped.compile().as_text()
    assert re.search(r'op_name="[^"]*/hot_tier/', compiled)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _lower()
    plain_compiled = plain.compile().as_text()
    assert not set(SCOPES) & _scopes(plain)
    # the same program: the lowering without locations, and the compiled
    # module without metadata
    assert scoped.as_text() == plain.as_text()
    assert _program(compiled) == _program(plain_compiled)


def test_hot_tier_holds_no_entry_values():
    """The hot tier tracks residency only: in the compiled decode no float
    op of the ``hot_tier`` scope is an entry wide, and no op anywhere holds
    a buffer's worth of entries ([..., buf or buf + 1, entry width]).  The
    96 positions keep the page table's width apart from both."""
    cfg = get_config("qwen2-1.5b").reduced()
    d_entry = build_model(cfg, mode="sac").kv_dim
    buf = cfg.sac.device_buffer_size
    assert 96 not in (d_entry, buf, buf + 1)
    text = _lower(seq_len=96).compile().as_text()
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([0-9,]*)\]([^\n]*)",
                     text, re.M)
    assert ops
    for name, dtype, shape, rest in ops:
        dims = [int(x) for x in shape.split(",") if x]
        if not dims or dims[-1] != d_entry:
            continue
        assert not (dtype in ("bf16", "f32") and "/hot_tier/" in rest), name
        assert len(dims) < 2 or dims[-2] not in (buf, buf + 1), (name, dims)


def test_deepseek_decode_names_its_expert_layer():
    """DeepSeek-V3.2's decode keeps the ten scopes (its MLA under
    ``attention``, the V3.2 indexer under ``indexer``, its dense layers'
    MLP under ``mlp``) and names the expert layer's parts ``router``,
    ``experts`` and ``shared_expert``."""
    cfg = get_config("deepseek-v32").reduced()
    sac = cfg.sac
    model = build_model(cfg, mode="sac", opts={
        "prefetch_width": sac.prefetch_width,
        "score_margin": sac.score_margin, "warmup_w": sac.warmup_entries})
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = model.serve_state_shapes(2, 64,
                                     device_buffer=sac.device_buffer_size)
    lowered = jax.jit(model.decode).lower(
        params, state, jax.ShapeDtypeStruct((2,), jnp.int32))
    names = set(SCOPES) | {"router", "experts", "shared_expert"}
    assert names <= _scopes(lowered)
    text = lowered.as_text(dialect="hlo", debug_info=True)
    paths = re.findall(r'op_name="([^"]*)"', text)
    # the expert layer's scopes sit outside ``mlp``
    assert not any("/mlp/" in p and "/experts/" in p for p in paths)
