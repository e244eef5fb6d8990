"""Pallas TPU kernel: scalar-prefetch sparse KV gather (SAC read path).

The CXL analogue on TPU (DESIGN.md §2): instead of warp-coalesced
``ld.global.b64`` loads, the top-k indices are scalar-prefetched into SMEM
*before* the kernel body runs, and drive the ``BlockSpec.index_map`` — so
the TPU DMA engine streams exactly the requested KV rows HBM->VMEM, one
descriptor per row, with no intermediate staging.  This is the TPU-native
form of a fine-grained, memory-semantic gather.

Grid: one step per gathered row.  The TPU lowering wants the last two
block dims to be (8k, 128k) or whole, so rows are viewed as [S, 1, d]:
kv blocks are (1, d) tiles of the row picked by ``idx[i]`` (leading dim
squeezed), out blocks the same at row ``i``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, kv_ref, out_ref):
    # the DMA engine has already landed kv[idx[i]] in VMEM; copy to out
    out_ref[...] = kv_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_kv(kv: jnp.ndarray, idx: jnp.ndarray, *, interpret: bool = False
              ) -> jnp.ndarray:
    """kv: [S, d] (pool shard, HBM); idx: [k] int32 -> [k, d].

    Out-of-range indices must be pre-clamped by the caller (the pooled
    fetch masks them after the gather).
    """
    k = idx.shape[0]
    S, d = kv.shape
    row = (pl.Squeezed(), 1, d)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[pl.BlockSpec(row, lambda i, idx_ref: (idx_ref[i], 0, 0))],
            out_specs=pl.BlockSpec(row, lambda i, idx_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((k, 1, d), kv.dtype),
        interpret=interpret,
    )(idx, kv.reshape(S, 1, d))
    return out.reshape(k, d)


def _gather_block_kernel(idx_ref, kv_ref, out_ref):
    out_ref[...] = kv_ref[...]


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def gather_kv_pages(kv: jnp.ndarray, page_idx: jnp.ndarray, *, page: int = 16,
                    interpret: bool = False) -> jnp.ndarray:
    """Page-granular gather: fetch whole pages of ``page`` consecutive rows.

    kv: [S, d] with S % page == 0; page_idx: [n_pages] page numbers
    -> [n_pages * page, d].  Fewer, larger DMA descriptors — the knob the
    paper's ``page_size`` controls.
    """
    n = page_idx.shape[0]
    d = kv.shape[-1]
    return pl.pallas_call(
        _gather_block_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((page, d),
                                   lambda i, idx_ref: (idx_ref[i], 0))],
            out_specs=pl.BlockSpec((page, d), lambda i, idx_ref: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n * page, d), kv.dtype),
        interpret=interpret,
    )(page_idx, kv)
