"""Top-k selection strategies over indexer scores.

``topk_select`` (re-exported from models/dsa.py) is the plain masked
``lax.top_k``.  ``topk_hierarchical`` is the *distributed* variant used as a
beyond-paper optimization (§Perf): when scores live sharded over the pool
axis, doing a local top-k per shard and re-selecting over the gathered
candidates moves ``shards * k`` score elements over the fabric instead of
the full ``[B, S]`` score matrix.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.dsa import NEG_INF, topk_select  # noqa: F401  (re-export)


def _hier_topk_local(scores, cache_len, *, k: int, axis: str):
    """shard_map body: local top-k then all-gather candidates + re-top-k.

    scores: [B_l, S_l]; cache_len: [B_l] -> (idx [B_l, k] global, valid).
    """
    S_local = scores.shape[-1]
    rank = jax.lax.axis_index(axis)
    base = rank * S_local
    pos = base + jnp.arange(S_local, dtype=jnp.int32)
    masked = jnp.where(pos[None, :] < cache_len[:, None], scores, NEG_INF)
    k_local = min(k, S_local)
    loc_scores, loc_idx = jax.lax.top_k(masked, k_local)
    loc_idx = loc_idx.astype(jnp.int32) + base
    # gather shards*k_local candidates everywhere, re-select
    cand_scores = jax.lax.all_gather(loc_scores, axis, axis=1, tiled=True)
    cand_idx = jax.lax.all_gather(loc_idx, axis, axis=1, tiled=True)
    top_scores, pos_in_cand = jax.lax.top_k(cand_scores, k)
    idx = jnp.take_along_axis(cand_idx, pos_in_cand, axis=1)
    valid = top_scores > NEG_INF / 2
    # position-sort the selected set (invalid lanes last), matching
    # dsa.topk_select: keeps sparse decode bit-exact vs dense and the
    # single-device path, and gathers monotone (see topk_select)
    order = jnp.argsort(jnp.where(valid, idx, jnp.int32(1 << 30)), axis=-1)
    return (jnp.take_along_axis(idx, order, axis=-1),
            jnp.take_along_axis(valid, order, axis=-1))


def make_hierarchical_topk(mesh: Mesh, k: int, *, batch_axes=("pod", "data"),
                           pool_axis: str = "model"):
    """(scores [B, S@pool_axis], cache_len [B]) -> (idx [B,k], valid [B,k])."""
    import functools
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    body = functools.partial(_hier_topk_local, k=k, axis=pool_axis)
    # replication check off (check_vma): the tiled
    # all_gather makes every pool-axis rank's candidate set identical, so
    # the re-top-k output IS replicated over the pool axis — but the
    # inference can't prove it.
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(batch, pool_axis), P(batch)),
                         out_specs=(P(batch, None), P(batch, None)),
                         check_vma=False)
