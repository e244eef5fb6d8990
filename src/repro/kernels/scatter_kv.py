"""Pallas TPU kernel: coalesced KV write-back (SAC write path).

The paper's GPU write path uses warp-coalesced ``st.global.b64`` stores to
push prefill KV into the CXL pool.  The TPU analogue: scalar-prefetched
destination indices drive the *output* BlockSpec, so each grid step DMAs
one entry row VMEM->HBM directly into its pool slot.  The pool buffer is
input/output-aliased — unwritten rows keep their previous contents
(in-place scatter).  Rows are viewed as [S, 1, d] so each block's last
two dims are whole, as the TPU lowering requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scatter_kernel(idx_ref, entries_ref, pool_ref, out_ref):
    out_ref[...] = entries_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_kv(pool: jnp.ndarray, entries: jnp.ndarray, idx: jnp.ndarray,
               *, interpret: bool = False) -> jnp.ndarray:
    """pool: [S, d]; entries: [k, d]; idx: [k] distinct rows -> updated pool."""
    k, d = entries.shape
    S = pool.shape[0]
    row = (pl.Squeezed(), 1, d)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k,),
            in_specs=[
                pl.BlockSpec(row, lambda i, idx_ref: (i, 0, 0)),  # entries
                pl.BlockSpec(row, lambda i, idx_ref: (idx_ref[i], 0, 0)),  # pool (aliased)
            ],
            out_specs=pl.BlockSpec(row, lambda i, idx_ref: (idx_ref[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, d), pool.dtype),
        input_output_aliases={2: 0},   # pool arg (after idx prefetch, entries)
        interpret=interpret,
    )(idx, entries.reshape(k, 1, d), pool.reshape(S, 1, d))
    return out.reshape(S, d)
