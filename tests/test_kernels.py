"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref.py
oracles.  Every call passes interpret=True, which executes the kernel body
on the CPU; the kernels default to compiling for the TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gather_kv import gather_kv, gather_kv_pages
from repro.kernels.indexer import indexer_scores
from repro.kernels.scatter_kv import scatter_kv
from repro.kernels.sparse_attn import sparse_attn

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("S,d,k", [(64, 32, 16), (128, 64, 32),
                                   (256, 128, 64), (64, 576, 8)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gather_kv_sweep(S, d, k, dtype):
    kv = jax.random.normal(KEY, (S, d), dtype)
    idx = jax.random.randint(KEY, (k,), 0, S)
    out = gather_kv(kv, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.gather_kv_ref(kv, idx),
                                          np.float32))


@pytest.mark.parametrize("page", [4, 16])
def test_gather_pages(page):
    S, d, n = 128, 64, 4
    kv = jax.random.normal(KEY, (S, d), jnp.bfloat16)
    pidx = jnp.array([0, 3, 5, 7], jnp.int32)
    out = gather_kv_pages(kv, pidx, page=page, interpret=True)
    expect = kv.reshape(S // page, page, d)[pidx].reshape(n * page, d)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32))


@pytest.mark.parametrize("S,di,H", [(512, 64, 4), (1024, 128, 8),
                                    (512, 32, 2)])
def test_indexer_sweep(S, di, H):
    q = jax.random.normal(KEY, (H, di), jnp.bfloat16)
    w = jax.random.normal(KEY, (H,), jnp.bfloat16)
    keys = jax.random.normal(KEY, (S, di), jnp.bfloat16)
    out = indexer_scores(q, w, keys, block_s=256, interpret=True)
    expect = ref.indexer_scores_ref(q, w, keys)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("k,H,dq,dv,block", [(256, 8, 64, 48, 128),
                                             (512, 16, 128, 128, 256),
                                             (128, 4, 576, 512, 128)])
def test_sparse_attn_sweep(k, H, dq, dv, block):
    q = jax.random.normal(KEY, (H, dq), jnp.bfloat16)
    keys = jax.random.normal(KEY, (k, dq), jnp.bfloat16)
    vals = jax.random.normal(KEY, (k, dv), jnp.bfloat16)
    valid = jax.random.bernoulli(KEY, 0.8, (k,)).at[0].set(True)
    bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)
    scale = 1.0 / np.sqrt(dq)
    out = sparse_attn(q, keys, vals, bias, scale=scale, block_k=block,
                      interpret=True)
    # oracle: dense softmax attention over valid entries
    s = (q.astype(jnp.float32) @ keys.astype(jnp.float32).T) * scale
    s = jnp.where(valid[None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    expect = p @ vals.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_scatter_inplace_semantics():
    S, d, k = 64, 32, 8
    pool = jax.random.normal(KEY, (S, d), jnp.bfloat16)
    entries = jax.random.normal(jax.random.PRNGKey(7), (k, d), jnp.bfloat16)
    idx = jnp.array([1, 5, 9, 13, 17, 21, 25, 29], jnp.int32)
    out = scatter_kv(pool, entries, idx, interpret=True)
    expect = ref.scatter_kv_ref(pool, entries, idx)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32))


# ---- batched ops wrappers: pallas vs ref dispatch equivalence ----

def test_ops_mla_equivalence():
    B, H, k, dc, dr = 2, 8, 32, 48, 16
    q_lat = jax.random.normal(KEY, (B, H, dc), jnp.bfloat16)
    q_pe = jax.random.normal(KEY, (B, H, dr), jnp.bfloat16)
    entries = jax.random.normal(KEY, (B, k, dc + dr), jnp.bfloat16)
    valid = jax.random.bernoulli(KEY, 0.7, (B, k)).at[:, 0].set(True)
    a = ops.batched_sparse_mla(q_lat, q_pe, entries, valid, dc=dc,
                               scale=0.11, use_pallas=True, interpret=True)
    b = ops.batched_sparse_mla(q_lat, q_pe, entries, valid, dc=dc,
                               scale=0.11, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-2, atol=2e-2)


def test_ops_gqa_equivalence():
    B, H, n_kv, hd, k = 2, 8, 4, 32, 16
    q = jax.random.normal(KEY, (B, H, hd), jnp.bfloat16)
    entries = jax.random.normal(KEY, (B, k, 2 * n_kv * hd), jnp.bfloat16)
    valid = jnp.ones((B, k), bool)
    a = ops.batched_sparse_gqa(q, entries, valid, n_kv=n_kv, use_pallas=True,
                               interpret=True)
    b = ops.batched_sparse_gqa(q, entries, valid, n_kv=n_kv, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=3e-2, atol=3e-2)


def test_gqa_ref_matches_model_decode():
    """ref.sparse_gqa_attn_ref is the same math as dsa.gqa_sparse_decode
    (modulo projections): cross-check on raw tensors."""
    from repro.models import dsa
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b").reduced()
    B, k = 2, 8
    entries = jax.random.normal(KEY, (B, k, dsa.gqa_entry_dim(cfg)),
                                jnp.bfloat16)
    valid = jnp.ones((B, k), bool)
    q = jax.random.normal(KEY, (B, cfg.n_heads, cfg.hd), jnp.bfloat16)
    out_ref = jax.vmap(
        lambda qq, ee, vv: ref.sparse_gqa_attn_ref(qq, ee, vv,
                                                   cfg.n_kv_heads)
    )(q, entries, valid)
    assert out_ref.shape == (B, cfg.n_heads, cfg.hd)
    assert not jnp.isnan(out_ref).any()
