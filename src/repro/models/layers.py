"""Shared model layers: norms, RoPE, SwiGLU, GQA attention (full / windowed /
chunked-causal), and the module-free parameter system used across the zoo.

Parameters are plain pytrees of jnp arrays.  Every leaf is declared through
``ParamSpec`` carrying *logical dims* (e.g. ``("L", "D", "F")``) from which
``distributed/sharding.py`` derives PartitionSpecs with divisibility checks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# module-free parameter system
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dims: Tuple[str, ...]            # logical dim names, len == len(shape)
    init: str = "normal"             # normal | zeros | ones
    scale: float = 1.0
    dtype: Any = DTYPE

    def materialize(self, key) -> jnp.ndarray:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        std = self.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, self.shape, jnp.float32) * std).astype(self.dtype)


def init_params(specs, key):
    """Materialize a pytree of ParamSpec into arrays with split keys."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [l.materialize(k) for l, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def spec_shapes(specs):
    """ParamSpec pytree -> ShapeDtypeStruct pytree (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# norms / activations / rope
# ---------------------------------------------------------------------------


def rms_norm(x, gamma, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's RoPE frequencies (arXiv:2309.00071, as DeepSeek-V3's
    inference code computes them): dims that turn fewer than ``beta_slow``
    times over the original context are slowed by ``factor``, those that
    turn more than ``beta_fast`` times keep their frequency, and a linear
    ramp blends the dims between."""
    def corr_dim(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    # in float64, rounded to float32 once at the end
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    smooth = 1 - ramp
    return (freqs / factor * (1 - smooth) + freqs * smooth).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor: 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float, freqs=None):
    """x: [..., S, H, hd] or [..., S, hd]; positions: [..., S].  Rotates
    adjacent pairs (2i, 2i+1) by ``freqs`` ([hd/2]; default plain RoPE of
    base ``theta``)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                          # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., S, hd/2]
    if x.ndim == ang.ndim + 1:                                  # head axis present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attn_param_specs(cfg, prefix_scale=1.0) -> Dict[str, ParamSpec]:
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": ParamSpec((d, nh * hd), ("D", "H")),
        "wk": ParamSpec((d, nkv * hd), ("D", "KV")),
        "wv": ParamSpec((d, nkv * hd), ("D", "KV")),
        "wo": ParamSpec((nh * hd, d), ("H", "D"), scale=prefix_scale),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((nh * hd,), ("H",), init="zeros")
        p["bk"] = ParamSpec((nkv * hd,), ("KV",), init="zeros")
        p["bv"] = ParamSpec((nkv * hd,), ("KV",), init="zeros")
    return p


def qkv_proj(p, x, cfg, positions):
    """x: [B, S, D] -> q [B, S, nh, hd], k/v [B, S, nkv, hd] with RoPE."""
    B, S, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def blocked_causal_attention(q, k, v, *, chunk: int = 1024,
                             window: int = 0, scale: Optional[float] = None,
                             head_block: int = 0) -> jnp.ndarray:
    """Memory-bounded causal attention via lax.scan over KV chunks
    (online softmax).  q,k,v: [B, S, H, hd] (k/v already head-repeated).
    ``window`` > 0 enables sliding-window masking; ``scale`` defaults to
    1/sqrt(hd).  ``head_block`` > 0 runs the heads in groups of that many,
    one after another (heads are independent, so each group's result is
    the one the whole would give): the float32 scores of one key chunk
    then span a group, not every head.
    """
    B, S, H, hd = q.shape
    if head_block and head_block < H:
        assert H % head_block == 0, (H, head_block)
        G = H // head_block

        def group(a):          # [B, S, H, d] -> [G, B, S, hb, d]
            return jnp.moveaxis(a.reshape(B, S, G, head_block, a.shape[-1]),
                                2, 0)
        out = jax.lax.map(
            lambda qkv: blocked_causal_attention(
                *qkv, chunk=chunk, window=window, scale=scale),
            (group(q), group(k), group(v)))
        return jnp.moveaxis(out, 0, 2).reshape(B, S, H, v.shape[-1])
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    n_chunks = max(S // chunk, 1)
    chunk = S // n_chunks
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)   # [B,H,S,hd]
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    kc = kf.reshape(B, H, n_chunks, chunk, hd).transpose(2, 0, 1, 3, 4)
    vc = vf.reshape(B, H, n_chunks, chunk, vf.shape[-1]).transpose(
        2, 0, 1, 3, 4)
    q_pos = jnp.arange(S)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        k_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj)                # [B,H,S,chunk]
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vj)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, H, S), -1e30, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32),
            jnp.zeros((B, H, S, vf.shape[-1]), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)             # [B,S,H,hd]


def dense_attention_block(p, x, cfg, positions, *, window: int = 0):
    """Full training/prefill attention for one layer. x: [B, S, D]."""
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, x, cfg, positions)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    out = blocked_causal_attention(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                                   window=window)
    return out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"], (k, v)


def decode_attention(q, k_cache, v_cache, length_mask):
    """Single-token decode attention over an explicit KV set.
    q: [B, nh, hd]; k/v_cache: [B, T, nkv, hd]; length_mask: [B, T] bool."""
    B, T, nkv, hd = k_cache.shape
    nh = q.shape[1]
    n_rep = nh // nkv
    scale = 1.0 / np.sqrt(hd)
    qf = q.astype(jnp.float32).reshape(B, nkv, n_rep, hd) * scale
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bgrd,btgd->bgrt", qf, kf)
    s = jnp.where(length_mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrt,btgd->bgrd", p, vf)
    return out.reshape(B, nh, hd).astype(k_cache.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_param_specs(cfg, d_ff: Optional[int] = None
                    ) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, f), ("D", "F")),
        "w_up": ParamSpec((d, f), ("D", "F")),
        "w_down": ParamSpec((f, d), ("F", "D")),
    }


def mlp_block(p, x):
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])
