"""jit'd batched wrappers over the Pallas kernels (+ ref dispatch).

``use_pallas=False`` routes to the pure-jnp oracles in ref.py (plain XLA);
``use_pallas=True`` runs the Pallas kernels, compiled for the TPU.  On the
CPU the kernels run only with ``interpret=True``, which is how
tests/test_kernels.py checks them against the oracles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.gather_kv import gather_kv, gather_kv_pages
from repro.kernels.indexer import indexer_scores as indexer_scores_pl
from repro.kernels.scatter_kv import scatter_kv
from repro.kernels.sparse_attn import NEG_INF, sparse_attn


def batched_gather(kv: jnp.ndarray, idx: jnp.ndarray, *,
                   use_pallas: bool = False, interpret: bool = False
                   ) -> jnp.ndarray:
    """kv: [B, S, d]; idx: [B, k] -> [B, k, d]."""
    if use_pallas:
        return jax.vmap(lambda a, b: gather_kv(a, b, interpret=interpret)
                        )(kv, idx)
    return jax.vmap(ref.gather_kv_ref)(kv, idx)


def batched_indexer_scores(q: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray,
                           *, use_pallas: bool = False,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, H, di]; w: [B, H]; keys: [B, S, di] -> [B, S] f32."""
    if use_pallas:
        return jax.vmap(lambda a, b, c: indexer_scores_pl(
            a, b, c, interpret=interpret))(q, w, keys)
    return jax.vmap(ref.indexer_scores_ref)(q, w, keys)


def batched_sparse_mla(q_lat: jnp.ndarray, q_pe: jnp.ndarray,
                       entries: jnp.ndarray, valid: jnp.ndarray, *,
                       dc: int, scale: float, use_pallas: bool = False,
                       interpret: bool = False) -> jnp.ndarray:
    """q_lat: [B,H,dc]; q_pe: [B,H,dr]; entries: [B,k,dc+dr]; valid: [B,k]
    -> out_lat [B,H,dc] f32."""
    if use_pallas:
        q = jnp.concatenate([q_lat, q_pe], axis=-1)
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
        return jax.vmap(lambda a, b, c, d: sparse_attn(
            a, b, c, d, scale=scale, interpret=interpret))(
                q, entries, entries[..., :dc], bias)
    return jax.vmap(functools.partial(ref.sparse_mla_attn_ref, dc=dc,
                                      scale=scale))(q_lat, q_pe, entries,
                                                    valid)


def batched_sparse_gqa(q: jnp.ndarray, entries: jnp.ndarray,
                       valid: jnp.ndarray, *, n_kv: int,
                       use_pallas: bool = False, interpret: bool = False
                       ) -> jnp.ndarray:
    """q: [B,H,hd]; entries: [B,k,2*n_kv*hd]; valid: [B,k] -> [B,H,hd]."""
    B, H, hd = q.shape
    k = entries.shape[1]
    if use_pallas:
        kv = entries.reshape(B, k, 2, n_kv, hd)
        keys = kv[:, :, 0].transpose(0, 2, 1, 3)       # [B, n_kv, k, hd]
        vals = kv[:, :, 1].transpose(0, 2, 1, 3)
        qg = q.reshape(B, n_kv, H // n_kv, hd)
        bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
        scale = 1.0 / np.sqrt(hd)

        def per_group(qr, kk, vv, bb):
            return sparse_attn(qr, kk, vv, bb, scale=scale,
                               interpret=interpret)

        out = jax.vmap(jax.vmap(per_group, in_axes=(0, 0, 0, None)),
                       in_axes=(0, 0, 0, 0))(qg, keys, vals, bias)
        return out.reshape(B, H, hd)
    return jax.vmap(functools.partial(ref.sparse_gqa_attn_ref, n_kv=n_kv)
                    )(q, entries, valid)


def batched_scatter(pool: jnp.ndarray, entries: jnp.ndarray,
                    idx: jnp.ndarray, *, use_pallas: bool = False,
                    interpret: bool = False) -> jnp.ndarray:
    """pool: [B,S,d]; entries: [B,k,d]; idx: [B,k] -> updated pool."""
    if use_pallas:
        return jax.vmap(lambda p, e, i: scatter_kv(p, e, i,
                                                   interpret=interpret)
                        )(pool, entries, idx)
    return jax.vmap(ref.scatter_kv_ref)(pool, entries, idx)
