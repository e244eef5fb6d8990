"""Pallas TPU kernel: top-k sparse attention with online softmax.

One kernel covers both attention forms used at decode time (DESIGN.md §4):

  - **absorbed MLA** (deepseek): q = concat(q_lat, q_pe) [H, dc+dr],
    keys = fetched latent entries [k, dc+dr], vals = entries[:, :dc];
  - **MQA / per-group GQA**: q [n_rep, hd], keys/vals [k, hd]
    (GQA = vmap over kv groups in ops.py).

Grid over k blocks; m/l/acc accumulators live in VMEM scratch and persist
across the sequential TPU grid (flash pattern: init at step 0, divide at
the last step).  ``bias`` carries the validity mask (-inf for invalid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _sparse_attn_kernel(q_ref, k_ref, v_ref, bias_ref, out_ref,
                        m_ref, l_ref, acc_ref, *, scale: float,
                        n_blocks: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                 # [H, dq]
    keys = k_ref[...].astype(jnp.float32)              # [bk, dq]
    vals = v_ref[...].astype(jnp.float32)              # [bk, dv]
    bias = bias_ref[...].astype(jnp.float32)           # [1, bk]

    s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ()))) * scale
    s = s + bias                                       # [H, bk]

    m_prev, l_prev = m_ref[...], l_ref[...]            # [H, 1]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)                             # [H, bk]
    corr = jnp.exp(m_prev - m_new)                     # [H, 1]
    l_new = l_prev * corr + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, vals, (((1,), (0,)), ((), ())))             # [H, dv]
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(i == n_blocks - 1)
    def _finish():
        out_ref[...] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_k", "interpret"))
def sparse_attn(q: jnp.ndarray, keys: jnp.ndarray, vals: jnp.ndarray,
                bias: jnp.ndarray, *, scale: float, block_k: int = 256,
                interpret: bool = False) -> jnp.ndarray:
    """q: [H, dq]; keys: [k, dq]; vals: [k, dv]; bias: [k] f32 (0 / -inf)
    -> out [H, dv] f32."""
    H, dq = q.shape
    k, dv = vals.shape
    block_k = min(block_k, k)
    assert k % block_k == 0, (k, block_k)
    n_blocks = k // block_k
    kern = functools.partial(_sparse_attn_kernel, scale=scale,
                             n_blocks=n_blocks)
    return pl.pallas_call(
        kern,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((H, dq), lambda i: (0, 0)),
            pl.BlockSpec((block_k, dq), lambda i: (i, 0)),
            pl.BlockSpec((block_k, dv), lambda i: (i, 0)),
            pl.BlockSpec((1, block_k), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((H, dv), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, keys, vals, bias.reshape(1, k))
