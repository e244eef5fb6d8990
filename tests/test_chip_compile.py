"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, so these tests compile the Pallas
kernels and the serving engine's jitted steps at real widths for a v5e that
is described, not attached.  They catch what interpret mode cannot: block
shapes the Mosaic lowering refuses, and programs that overflow the chip's
16 GB of HBM.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every pytest worker imports
every test file.  All chip compiles stay in this one file for that reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.pool import make_pooled_fetch
from repro.kernels.gather_kv import gather_kv, gather_kv_pages
from repro.kernels.indexer import indexer_scores
from repro.kernels.scatter_kv import scatter_kv
from repro.kernels.sparse_attn import sparse_attn
from repro.models.model import build_model
from repro.serving.engine import Engine

HBM_BYTES = 16e9          # one TPU v5e chip
S, D_ENTRY = 32768, 576   # pool rows and an MLA latent+rope entry
# the serving size chip_smoke.py runs: 4 slots x 8192 context, 7168 prompt
SLOTS, MAX_CTX, PROMPT = 4, 8192, 7168


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel,k", [("gather_kv", 2048), ("gather_kv", 16),
                                      ("scatter_kv", 2048),
                                      ("scatter_kv", 16)])
def test_row_kernels_compile(one_chip, kernel, k):
    kv = _sds((S, D_ENTRY), jnp.bfloat16, one_chip)
    idx = _sds((k,), jnp.int32, one_chip)
    if kernel == "gather_kv":
        compiled = _compile(gather_kv, kv, idx)
    else:
        entries = _sds((k, D_ENTRY), jnp.bfloat16, one_chip)
        compiled = _compile(scatter_kv, kv, entries, idx)
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_kv_pages_compiles(one_chip):
    kv = _sds((S, D_ENTRY), jnp.bfloat16, one_chip)
    pages = _sds((2048 // 16,), jnp.int32, one_chip)
    compiled = _compile(lambda a, b: gather_kv_pages(a, b, page=16), kv, pages)
    assert "tpu_custom_call" in compiled.as_text()


def test_indexer_scores_compiles(one_chip):
    H, di = 64, 128
    compiled = _compile(indexer_scores, _sds((H, di), jnp.bfloat16, one_chip),
                        _sds((H,), jnp.bfloat16, one_chip),
                        _sds((S, di), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_sparse_attn_compiles(one_chip):
    H, dq, dv, k = 128, 576, 512, 2048
    compiled = _compile(
        lambda q, kk, v, b: sparse_attn(q, kk, v, b, scale=dq ** -0.5),
        _sds((H, dq), jnp.bfloat16, one_chip),
        _sds((k, dq), jnp.bfloat16, one_chip),
        _sds((k, dv), jnp.bfloat16, one_chip),
        _sds((k,), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen(one_chip):
    """Qwen2-1.5B at published width, built as ``Engine(..., prefetch=True)``
    builds it, with parameter and serve-state shapes on one chip."""
    cfg = get_config("qwen2-1.5b")
    sac = cfg.sac
    model = build_model(cfg, mode="sac", opts={
        "prefetch_width": sac.prefetch_width,
        "score_margin": sac.score_margin,
        "warmup_w": sac.warmup_entries})
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    state = _on(model.serve_state_shapes(
        SLOTS, MAX_CTX, device_buffer=sac.device_buffer_size), one_chip)
    return cfg, model, params, state


def test_qwen_decode_fits_one_chip(qwen, one_chip):
    cfg, model, params, state = qwen
    compiled = _compile(model.decode, params, state,
                        _sds((SLOTS,), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_qwen_prefill_compiles(qwen, one_chip):
    cfg, model, params, _ = qwen
    compiled = _compile(lambda p, t: model.prefill(p, t), params,
                        _sds((1, PROMPT), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_engine_warmup_compiles(qwen, one_chip):
    """The prefill warm-up jit: one plan lane per layer for every score
    seed plus every radix-tail seed."""
    cfg, model, _, state = qwen
    width = cfg.sac.warmup_entries + cfg.sac.warmup_radix
    plan = (model.n_kv, width)
    _compile(Engine._warm_apply, state["hot_buf"],
             _sds((), jnp.int32, one_chip), _sds(plan, jnp.int32, one_chip),
             _sds(plan, jnp.bool_, one_chip))


def test_pooled_fetch_compiles_on_2x2(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    fetch = make_pooled_fetch(mesh, batch_axes=(), pool_axis="model")
    B, k = 4, 2048
    pool = _sds((B, S, D_ENTRY), jnp.bfloat16,
                NamedSharding(mesh, P(None, "model", None)))
    idx = _sds((B, k), jnp.int32, NamedSharding(mesh, P()))
    compiled = _compile(fetch, pool, idx)
    assert "all-reduce" in compiled.as_text()


@pytest.fixture(scope="module")
def deepseek(one_chip):
    """One chip's share of DeepSeek-V3.2 as the benchmark serves it
    (chipbench/configs/deepseek-v32.json: 3 dense + 4 expert layers, 8
    held of 256 experts, 16160 ids), built as ``Engine(..., prefetch=True)``
    builds it, with parameter and serve-state shapes on one chip."""
    import dataclasses
    cfg = dataclasses.replace(get_config("deepseek-v32"), n_layers=7,
                              n_experts_held=8, vocab=16160)
    sac = cfg.sac
    model = build_model(cfg, mode="sac", opts={
        "prefetch_width": sac.prefetch_width,
        "score_margin": sac.score_margin,
        "warmup_w": sac.warmup_entries})
    params = _on(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    state = _on(model.serve_state_shapes(
        SLOTS, MAX_CTX, device_buffer=sac.device_buffer_size), one_chip)
    return cfg, model, params, state


def test_deepseek_share_decode_fits_one_chip(deepseek, one_chip):
    cfg, model, params, state = deepseek
    compiled = _compile(model.decode, params, state,
                        _sds((SLOTS,), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_deepseek_share_prefill_fits_one_chip(deepseek, one_chip):
    """The prefill at the longest prompt, beside the engine's 4-slot
    state, which lives on the chip while a request is admitted."""
    cfg, model, params, state = deepseek
    compiled = _compile(lambda p, t: model.prefill(p, t), params,
                        _sds((1, PROMPT), jnp.int32, one_chip))
    mem = compiled.memory_analysis()
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(state))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + state_bytes)
    assert total < HBM_BYTES, total
