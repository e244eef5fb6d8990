"""Pallas TPU kernel: lightning indexer scoring.

scores[s] = sum_h w[h] * ReLU(q[h] . keys[s]) / sqrt(di)

Grid over S blocks; each step does a [block_s, di] x [di, H] matmul on the
MXU, ReLU on the VPU, and a weighted reduction over heads.  q/w are small
and live fully in VMEM (index_map pinned to block 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _indexer_kernel(keys_ref, q_ref, w_ref, out_ref, *, di: int):
    keys = keys_ref[...].astype(jnp.float32)          # [bs, di]
    q = q_ref[...].astype(jnp.float32)                # [H, di]
    w = w_ref[...].astype(jnp.float32)                # [1, H]
    logits = jax.nn.relu(
        jax.lax.dot_general(keys, q, (((1,), (1,)), ((), ())))
    ) * (1.0 / np.sqrt(di))                           # [bs, H]
    out_ref[...] = jax.lax.dot_general(
        logits, w, (((1,), (1,)), ((), ()))).reshape(out_ref.shape)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def indexer_scores(q: jnp.ndarray, w: jnp.ndarray, keys: jnp.ndarray, *,
                   block_s: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: [H, di]; w: [H]; keys: [S, di] -> scores [S] f32."""
    S, di = keys.shape
    H = q.shape[0]
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    kern = functools.partial(_indexer_kernel, di=di)
    out = pl.pallas_call(
        kern,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, di), lambda i: (i, 0)),
            pl.BlockSpec((H, di), lambda i: (0, 0)),
            pl.BlockSpec((1, H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, 1), jnp.float32),
        interpret=interpret,
    )(keys, q, w.reshape(1, H))
    return out[:, 0]
