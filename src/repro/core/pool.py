"""Disaggregated KV-cache pool (the paper's CXL pool, mapped to TPU).

The pool is a logical array ``[B, S, d]`` per layer whose sequence axis is
sharded across the ``model`` mesh axis — the pod's aggregate HBM plays the
role of the CXL memory pool, and ICI plays the role of the CXL fabric
(DESIGN.md §2).  The **read path** is a fine-grained gather of the per-layer
top-k entries:

  - each pool shard gathers the indices that fall inside its range
    (clamped + masked ``take_along_axis`` — on real TPU this is the Pallas
    scalar-prefetch DMA gather, ``kernels/gather_kv.py``),
  - a single ``psum`` over the ``model`` axis assembles the full ``[B,k,d]``
    result on every TP rank (which is what TP attention needs anyway).

Per step this moves exactly ``k * entry_bytes`` per request over the
fabric — the paper's "fetch only the top-k on demand" — instead of the
full-prefix transfer an RDMA-style full-prefetch system performs.

The **write path** scatters each request's newly decoded entry to the shard
that owns its position (a masked in-place update, no collective: the new
entry is produced TP-replicated by the layer, every shard keeps its slice).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

FetchFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


# ---------------------------------------------------------------------------
# read path
# ---------------------------------------------------------------------------


def local_fetch(pool_layer: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Single-shard gather. pool_layer: [B, S, d]; idx: [B, k] -> [B, k, d]."""
    return jnp.take_along_axis(pool_layer, idx[..., None], axis=1)


def _pooled_fetch_local(pool, idx, *, axis: str):
    """shard_map body: masked local gather + psum over the pool axis.

    The optimization barrier pins the gather -> mask -> psum order: the
    CPU backend's bf16 all-reduce is wrapped in converts that the XLA
    simplifier otherwise commutes through the gather and hoists out of
    the layer scan — materializing an f32 copy of the ENTIRE pool
    (§Perf iteration C3).  On TPU the psum is native bf16 and the
    barrier is a no-op.
    """
    S_local = pool.shape[1]
    rank = jax.lax.axis_index(axis)
    local = idx - rank * S_local
    in_bounds = (local >= 0) & (local < S_local)
    local_c = jnp.clip(local, 0, S_local - 1)
    vals = jnp.take_along_axis(pool, local_c[..., None], axis=1)
    vals = jnp.where(in_bounds[..., None], vals, 0)
    vals = jax.lax.optimization_barrier(vals)
    return jax.lax.psum(vals, axis)


def make_pooled_fetch(mesh: Mesh, *, batch_axes=("pod", "data"),
                      pool_axis: str = "model") -> FetchFn:
    """Build the pooled-HBM fetch: [B@batch_axes, S@pool_axis, d] x [B, k]
    -> [B, k, d] replicated over pool_axis (ready for TP attention)."""
    batch = tuple(a for a in batch_axes if a in mesh.axis_names)
    spec_pool = P(batch, pool_axis, None)
    spec_idx = P(batch, None)
    spec_out = P(batch, None, None)
    body = functools.partial(_pooled_fetch_local, axis=pool_axis)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec_pool, spec_idx),
                         out_specs=spec_out)


def make_fetch_fn(mesh: Optional[Mesh], backend: str = "local",
                  **kw) -> FetchFn:
    """Resolve the fetch callback for a backend name.

    ``local``      — single-shard take_along_axis (tests, host_dram engine).
    ``pooled_hbm`` — shard_map collective gather over the pool axis.
    """
    if backend == "pooled_hbm":
        if mesh is None:
            raise ValueError("pooled_hbm backend requires a mesh")
        return make_pooled_fetch(mesh, **kw)
    if backend in ("local", "host_dram"):
        return local_fetch
    raise ValueError(f"unknown pool backend {backend!r}")


# ---------------------------------------------------------------------------
# write path
# ---------------------------------------------------------------------------


def pool_write(pool: jnp.ndarray, new_entries: jnp.ndarray,
               pos: jnp.ndarray) -> jnp.ndarray:
    """Write one new entry per (layer, request) at per-request positions.

    pool: [L, B, S, d]; new_entries: [L, B, d]; pos: [B] -> updated pool.

    Implemented as a masked select rather than lax.scatter (§Perf
    iteration C2): elementwise select keeps the S axis sharded with zero
    collectives (each pool shard blends only its own rows), preserves the
    pool dtype (XLA:CPU lowers bf16 scatter through full f32 pool copies),
    and aliases the donated pool buffer.
    """
    S = pool.shape[2]
    with jax.named_scope("pool_write"):
        pos_c = jnp.clip(pos, 0, S - 1)
        mask = (jnp.arange(S, dtype=jnp.int32)[None, :]
                == pos_c[:, None])                       # [B, S]
        return jnp.where(mask[None, :, :, None],
                         new_entries.astype(pool.dtype)[:, :, None, :], pool)


def pool_write_prefill(pool: jnp.ndarray, entries: jnp.ndarray,
                       offset: int = 0) -> jnp.ndarray:
    """Bulk layer-wise write of prefill entries (the paper's GPU write path).

    pool: [L, B, S, d]; entries: [L, B, T, d] -> pool with [offset:offset+T)
    filled.  A contiguous dynamic-update-slice: each pool shard receives its
    slice of the new entries (reshard on entry, no host staging).
    """
    return jax.lax.dynamic_update_slice(
        pool, entries.astype(pool.dtype), (0, 0, offset, 0))


# ---------------------------------------------------------------------------
# device interleaving (paper §4.3.3) — lives in the shared placement
# substrate; re-exported here for back-compat.
# ---------------------------------------------------------------------------

from repro.core.placement import interleaved_assignment  # noqa: E402,F401
