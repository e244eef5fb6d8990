"""DeepSeek Sparse Attention (DSA) building blocks + sparse decode paths.

This implements the model-side machinery the SAC paper serves:

  - **Lightning indexer** (paper Fig 1): low-dim projected keys stored per
    token; at decode time the current query scores *all* cached positions
    ``I[t,s] = sum_h w[t,h] * ReLU(q_idx[t,h] . k_idx[s])`` and the top-k
    positions are selected.  MLA configurations run DeepSeek-V3.2's
    indexer (its ``inference/model.py``): the query from the query latent,
    a LayerNorm on the key, RoPE on the first ``idx_rope_dim`` dims of
    both, and head weights ``weights_proj(x) / sqrt(n_heads)``.
  - **MLA** (multi-head latent attention): prefill runs the non-absorbed
    form and emits the latent cache entry ``(c_kv, k_rope)`` = 512+64 dims;
    decode runs the *absorbed* form directly over fetched latent entries.
  - **GQA sparse decode**: the same top-k machinery applied to ordinary
    GQA KV entries (how SAC generalizes beyond DeepSeek, DESIGN.md §5).

All decode paths consume a ``fetch_fn(pool_layer, idx) -> [B, k, d]``
injected by the runtime: single-device ``take_along_axis`` for tests, the
shard_map pooled-HBM collective gather (core/pool.py) at scale.  That
callback *is* the SAC read path.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import (ParamSpec, apply_rope, rms_norm,
                                 yarn_freqs, yarn_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# lightning indexer
# ---------------------------------------------------------------------------


def indexer_param_specs(cfg) -> Dict[str, ParamSpec]:
    d, ni, di = cfg.d_model, cfg.sac.n_idx_heads, cfg.sac.d_idx
    if cfg.mla:
        return {
            "wq_b": ParamSpec((cfg.q_lora_rank, ni * di), ("C", "H")),
            "wk": ParamSpec((d, di), ("D", "C")),
            "k_norm_g": ParamSpec((di,), ("C",), init="ones"),
            "k_norm_b": ParamSpec((di,), ("C",), init="zeros"),
            "weights_proj": ParamSpec((d, ni), ("D", "C")),
        }
    return {
        "wq_idx": ParamSpec((d, ni * di), ("D", "H")),
        "wk_idx": ParamSpec((d, di), ("D", "C")),
        "w_w": ParamSpec((d, ni), ("D", "C"), scale=0.1),
    }


def _layer_norm(x, g, b, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _idx_rope(x, positions, cfg):
    """RoPE on the first ``idx_rope_dim`` dims of V3.2 indexer vectors."""
    r = cfg.idx_rope_dim
    if not r:
        return x
    pe = apply_rope(x[..., :r], positions, cfg.rope_theta,
                    freqs=rope_freqs_of(cfg, r))
    return jnp.concatenate([pe, x[..., r:]], axis=-1)


def indexer_keys(p, x, cfg=None, positions=None) -> jnp.ndarray:
    """Per-token indexer keys. x: [..., D] -> [..., d_idx]; ``positions``
    ([...]) place the V3.2 keys of MLA configurations."""
    if cfg is not None and cfg.mla:
        k = _layer_norm(x @ p["wk"], p["k_norm_g"], p["k_norm_b"])
        return _idx_rope(k, positions, cfg)
    return x @ p["wk_idx"]


def indexer_scores(p, xq, idx_keys, cfg, p_attn=None, positions=None
                   ) -> jnp.ndarray:
    """Score all cached positions against the current query token.

    xq: [B, D] (query-token activations); idx_keys: [B, S, d_idx]
    -> scores [B, S] (f32).  MLA configurations take the query from the
    attention's query latent (``p_attn``) at ``positions`` ([B]).
    """
    B = xq.shape[0]
    ni, di = cfg.sac.n_idx_heads, cfg.sac.d_idx
    if cfg.mla:
        q = (mla_q_latent(p_attn, xq) @ p["wq_b"]).reshape(B, ni, di)
        q = _idx_rope(q, positions[:, None], cfg).astype(jnp.float32)
        w = (xq @ p["weights_proj"]).astype(jnp.float32) / np.sqrt(ni)
        logits = jnp.einsum("bhd,bsd->bhs", q,
                            idx_keys.astype(jnp.float32))
        logits = jax.nn.relu(logits) / np.sqrt(di)
        return jnp.einsum("bh,bhs->bs", w, logits)
    q = (xq @ p["wq_idx"]).reshape(B, ni, di).astype(jnp.float32)
    w = (xq @ p["w_w"]).astype(jnp.float32)                      # [B, ni]
    logits = jnp.einsum("bhd,bsd->bhs", q, idx_keys.astype(jnp.float32))
    logits = jax.nn.relu(logits) / np.sqrt(di)
    return jnp.einsum("bh,bhs->bs", w, logits)                   # [B, S]


def topk_select(scores: jnp.ndarray, cache_len: jnp.ndarray, k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mask positions >= cache_len, take top-k.

    scores: [B, S]; cache_len: [B] -> (idx [B, k] int32, valid [B, k] bool).
    """
    S = scores.shape[-1]
    pos = jnp.arange(S, dtype=jnp.int32)
    masked = jnp.where(pos[None, :] < cache_len[:, None], scores, NEG_INF)
    top_scores, idx = jax.lax.top_k(masked, min(k, S))
    valid = top_scores > NEG_INF / 2
    # position-sort the selected set (invalid lanes pushed last): the
    # sparse candidate order then matches the pool order, so with k >=
    # context the sparse decode is bit-exact vs dense (float accumulation
    # order is identical), and real gathers walk the pool monotonically
    return _position_sort(idx.astype(jnp.int32), valid, S)


def _position_sort(idx: jnp.ndarray, valid: jnp.ndarray, S: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort a selected set by position (invalid lanes pushed last)."""
    order = jnp.argsort(jnp.where(valid, idx, S), axis=-1)
    return (jnp.take_along_axis(idx, order, axis=-1),
            jnp.take_along_axis(valid, order, axis=-1))


def _spec_tail(top_scores, idx, k: int, width: int,
               score_margin: float = -1.0
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Ranks [k, k+width) of a top-(k+width) result, padded to width.

    ``score_margin >= 0`` switches the tail from a pure rank window to
    **score-threshold** selection: a tail entry only qualifies while its
    score is within ``margin * (s_max - s_k)`` of the k-th demand score
    ``s_k`` (scale-free — indexer score magnitudes vary per model).  A
    flat score landscape near the cut keeps the full window; a steep
    drop-off after rank k stops speculation early, so cheap steps stop
    fetching useless tail entries.  Negative margin = rank-only (PR 2
    semantics).
    """
    lo = min(k, idx.shape[-1])
    tail_idx = idx[..., lo:].astype(jnp.int32)
    tail_scores = top_scores[..., lo:]
    tail_valid = tail_scores > NEG_INF / 2
    if score_margin >= 0 and lo > 0:
        s_max = top_scores[..., :1]
        s_k = top_scores[..., lo - 1:lo]
        thr = s_k - score_margin * (s_max - s_k)
        tail_valid = tail_valid & (tail_scores >= thr)
    pad = width - tail_idx.shape[-1]
    if pad > 0:
        tail_idx = jnp.pad(tail_idx, ((0, 0), (0, pad)))
        tail_valid = jnp.pad(tail_valid, ((0, 0), (0, pad)))
    return tail_idx, tail_valid


def speculate_next_topk(scores: jnp.ndarray, cache_len: jnp.ndarray,
                        k: int, width: int, score_margin: float = -1.0
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative next-step candidates: ranks [k, k+width) of this step's
    indexer scores.

    Consecutive steps' score landscapes drift slowly, so the positions
    just below the current top-k cut are the most likely *entrants* of
    the next step's top-k — the fetch pipeline (serving/prefetch.py)
    warm-inserts them into the HiSparse hot tier so next step's churn
    hits instead of missing.  scores: [B, S]; -> (idx [B, width] int32,
    valid [B, width]); lanes beyond the candidate count are invalid.

    Standalone variant (used when the demand selection is injected via
    ``topk_fn``); the default decode path uses the fused
    :func:`topk_select_with_tail` to avoid a second top-k.
    """
    S = scores.shape[-1]
    pos = jnp.arange(S, dtype=jnp.int32)
    masked = jnp.where(pos[None, :] < cache_len[:, None], scores, NEG_INF)
    kk = min(k + width, S)
    top_scores, idx = jax.lax.top_k(masked, kk)
    return _spec_tail(top_scores, idx, k, width, score_margin)


def topk_select_with_tail(scores: jnp.ndarray, cache_len: jnp.ndarray,
                          k: int, width: int, score_margin: float = -1.0):
    """Fused demand top-k + speculation tail: ONE ``top_k(k+width)``
    serves both.

    ``top_k`` orders by (score desc, index asc), so the first
    ``min(k, S)`` lanes of the larger sort are exactly
    :func:`topk_select`'s set — position-sorted identically, the demand
    half is bit-identical to the unfused path (sparse decode results do
    not depend on whether speculation runs).  ``score_margin`` applies
    score-threshold selection to the tail only (see :func:`_spec_tail`);
    the demand half never depends on it.  Returns
    ``(idx [B, min(k,S)], valid, tail_idx [B, width], tail_valid)``.
    """
    S = scores.shape[-1]
    pos = jnp.arange(S, dtype=jnp.int32)
    masked = jnp.where(pos[None, :] < cache_len[:, None], scores, NEG_INF)
    kk = min(k + width, S)
    top_scores, idx = jax.lax.top_k(masked, kk)
    lo = min(k, kk)
    d_idx = idx[..., :lo].astype(jnp.int32)
    d_valid = top_scores[..., :lo] > NEG_INF / 2
    d_idx, d_valid = _position_sort(d_idx, d_valid, S)
    return d_idx, d_valid, *_spec_tail(top_scores, idx, k, width,
                                       score_margin)


def budget_mask(valid: jnp.ndarray, budget: jnp.ndarray) -> jnp.ndarray:
    """Cap a speculation candidate set to a per-request granted budget.

    valid: [B, w] candidate lanes (score/rank-ordered best-first);
    budget: [B] int32 granted widths from the fabric budget arbiter
    (serving/arbiter.py).  Only the first ``budget[b]`` lanes survive —
    lanes are best-first, so the cap drops the least likely entrants.
    Budgets shape *speculation traffic* only; demand selection (and thus
    decoded tokens) never flows through this mask.
    """
    lanes = jnp.arange(valid.shape[-1], dtype=jnp.int32)
    return valid & (lanes[None, :] < budget[:, None].astype(jnp.int32))


# ---------------------------------------------------------------------------
# MLA parameters
# ---------------------------------------------------------------------------


def mla_param_specs(cfg) -> Dict[str, ParamSpec]:
    d, nh, hd = cfg.d_model, cfg.n_heads, cfg.hd
    dc, dr, qr = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.q_lora_rank
    return {
        "w_dq": ParamSpec((d, qr), ("D", "C")),
        "q_norm_g": ParamSpec((qr,), ("C",), init="ones"),
        "w_uq": ParamSpec((qr, nh * (hd + dr)), ("C", "H")),
        "w_dkv": ParamSpec((d, dc + dr), ("D", "C")),
        "kv_norm_g": ParamSpec((dc,), ("C",), init="ones"),
        "w_uk": ParamSpec((dc, nh * hd), ("C", "H")),
        "w_uv": ParamSpec((dc, nh * hd), ("C", "H")),
        "wo": ParamSpec((nh * hd, d), ("H", "D")),
    }


def rope_freqs_of(cfg, dim: int):
    """RoPE frequencies of a ``dim``-wide MLA or indexer rope part: YaRN's
    where the configuration scales its context, else None (plain)."""
    if cfg.rope_factor <= 1:
        return None
    return jnp.asarray(yarn_freqs(dim, cfg.rope_theta, cfg.rope_factor,
                                  cfg.rope_original_max_pos,
                                  cfg.rope_beta_fast, cfg.rope_beta_slow))


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale squared."""
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
         if cfg.rope_mscale_all_dim else 1.0)
    return m * m / np.sqrt(cfg.hd + cfg.qk_rope_dim)


def _mla_rope(x, positions, cfg):
    out = apply_rope(x, positions, cfg.rope_theta,
                     freqs=rope_freqs_of(cfg, x.shape[-1]))
    if cfg.rope_factor > 1 and cfg.rope_mscale_all_dim:
        # YaRN scales cos and sin by mscale / mscale_all_dim (1 where
        # they are equal, as in DeepSeek-V3.2)
        att = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
               / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
        if att != 1.0:
            out = (out.astype(jnp.float32) * att).astype(x.dtype)
    return out


def mla_q_latent(p, x):
    """The normed query latent [.., q_lora_rank] (V3.2's indexer reads it)."""
    return rms_norm(x @ p["w_dq"], p["q_norm_g"])


def mla_q_proj(p, x, cfg, positions):
    """x: [B(, S), D] -> q_nope [B(,S),nh,hd], q_pe [B(,S),nh,dr] (roped)."""
    nh, hd, dr = cfg.n_heads, cfg.hd, cfg.qk_rope_dim
    lead = x.shape[:-1]
    q = mla_q_latent(p, x) @ p["w_uq"]
    q = q.reshape(*lead, nh, hd + dr)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    q_pe = _mla_rope(q_pe, positions, cfg)
    return q_nope, q_pe


def mla_kv_entry(p, x, cfg, positions):
    """Latent cache entry for each token: [.., dc+dr] (c_kv normed, k_pe roped)."""
    dc = cfg.kv_lora_rank
    kv = x @ p["w_dkv"]
    c, k_pe = kv[..., :dc], kv[..., dc:]
    c = rms_norm(c, p["kv_norm_g"])
    k_pe = _mla_rope(k_pe, positions, cfg)
    return jnp.concatenate([c, k_pe], axis=-1)


# heads a prefill attends at once: at 128 heads and a 7168-token prompt one
# key chunk's float32 scores over every head are 3.8 GB, over 16 0.47 GB
MLA_HEAD_BLOCK = 16


def mla_prefill_attention(p, x, cfg, positions, *, chunk: int = 1024):
    """Non-absorbed MLA over a full sequence (training / prefill).

    x: [B, S, D] -> (out [B, S, D], cache_entries [B, S, dc+dr]).
    """
    from repro.models.layers import blocked_causal_attention

    B, S, D = x.shape
    nh, hd, dr, dc = cfg.n_heads, cfg.hd, cfg.qk_rope_dim, cfg.kv_lora_rank
    q_nope, q_pe = mla_q_proj(p, x, cfg, positions)
    entry = mla_kv_entry(p, x, cfg, positions)
    c, k_pe = entry[..., :dc], entry[..., dc:]
    k_nope = (c @ p["w_uk"]).reshape(B, S, nh, hd)
    v = (c @ p["w_uv"]).reshape(B, S, nh, hd)
    k_pe_b = jnp.broadcast_to(k_pe[:, :, None, :], (B, S, nh, dr))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, k_pe_b], axis=-1)
    out = blocked_causal_attention(q, k, v, chunk=chunk,
                                   scale=mla_softmax_scale(cfg),
                                   head_block=MLA_HEAD_BLOCK)
    return out.reshape(B, S, nh * hd) @ p["wo"], entry


def mla_absorbed_decode(p, xq, cfg, fetched, valid, positions):
    """Absorbed MLA decode over fetched latent entries.

    xq: [B, D]; fetched: [B, k, dc+dr]; valid: [B, k] bool;
    positions: [B] (query positions) -> out [B, D].
    """
    B = xq.shape[0]
    nh, hd, dr, dc = cfg.n_heads, cfg.hd, cfg.qk_rope_dim, cfg.kv_lora_rank
    q_nope, q_pe = mla_q_proj(p, xq, cfg, positions)             # [B,nh,hd],[B,nh,dr]
    w_uk = p["w_uk"].reshape(dc, nh, hd)
    # absorb: q_lat[b,h,c] = sum_d q_nope[b,h,d] * w_uk[c,h,d]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    c = fetched[..., :dc].astype(jnp.float32)                    # [B,k,dc]
    k_pe = fetched[..., dc:].astype(jnp.float32)                 # [B,k,dr]
    scale = mla_softmax_scale(cfg)
    s = (jnp.einsum("bhc,bkc->bhk", q_lat, c)
         + jnp.einsum("bhr,bkr->bhk", q_pe.astype(jnp.float32), k_pe)) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhk,bkc->bhc", pattn, c)                 # [B,nh,dc]
    w_uv = p["w_uv"].reshape(dc, nh, hd)
    out = jnp.einsum("bhc,chd->bhd", o_lat, w_uv.astype(jnp.float32))
    out = out.reshape(B, nh * hd).astype(xq.dtype)
    return out @ p["wo"]


# ---------------------------------------------------------------------------
# GQA sparse / dense decode over pool entries
# ---------------------------------------------------------------------------


def gqa_entry_dim(cfg) -> int:
    return 2 * cfg.n_kv_heads * cfg.hd


def gqa_kv_entry(p, x, cfg, positions):
    """Pool entry for GQA archs: stacked (roped k, v) [.., 2*nkv*hd].

    Layout matches the decode-side ``reshape(B, k, 2, nkv, hd)``.
    """
    lead = x.shape[:-1]
    nkv, hd = cfg.n_kv_heads, cfg.hd
    k = (x @ p["wk"]).reshape(*lead, nkv, hd)
    v = (x @ p["wv"]).reshape(*lead, nkv, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].reshape(nkv, hd)
        v = v + p["bv"].reshape(nkv, hd)
    k = apply_rope(k, positions, cfg.rope_theta)
    return jnp.stack([k, v], axis=-3).reshape(*lead, 2 * nkv * hd)


def pack_kv_entry(k, v):
    """[.., S, nkv, hd] k/v (k already roped) -> [.., S, 2*nkv*hd] entries."""
    lead = k.shape[:-2]
    nkv, hd = k.shape[-2:]
    return jnp.stack([k, v], axis=-3).reshape(*lead, 2 * nkv * hd)


def gqa_q_proj(p, x, cfg, positions):
    lead = x.shape[:-1]
    nh, hd = cfg.n_heads, cfg.hd
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(*lead, nh, hd)
    return apply_rope(q, positions, cfg.rope_theta)


def gqa_sparse_decode(p, xq, cfg, fetched, valid, positions):
    """GQA attention over fetched top-k entries.

    xq: [B, D]; fetched: [B, k, 2*nkv*hd]; valid: [B, k] -> [B, D].
    """
    B, k = fetched.shape[:2]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = gqa_q_proj(p, xq, cfg, positions)                        # [B,nh,hd]
    kv = fetched.reshape(B, k, 2, nkv, hd)
    keys = kv[:, :, 0].astype(jnp.float32)                       # [B,k,nkv,hd]
    vals = kv[:, :, 1].astype(jnp.float32)
    n_rep = nh // nkv
    qf = q.astype(jnp.float32).reshape(B, nkv, n_rep, hd) / np.sqrt(hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, keys)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", pattn, vals)
    out = out.reshape(B, nh * hd).astype(xq.dtype)
    return out @ p["wo"]


def gqa_dense_decode(p, xq, cfg, pool_layer, cache_len, positions):
    """Dense decode over the full pool slice (RDMA-full-prefetch analogue /
    upper-bound baseline).  pool_layer: [B, S, 2*nkv*hd]."""
    B, S, _ = pool_layer.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = gqa_q_proj(p, xq, cfg, positions)
    kv = pool_layer.reshape(B, S, 2, nkv, hd)
    keys = kv[:, :, 0].astype(jnp.float32)
    vals = kv[:, :, 1].astype(jnp.float32)
    n_rep = nh // nkv
    qf = q.astype(jnp.float32).reshape(B, nkv, n_rep, hd) / np.sqrt(hd)
    s = jnp.einsum("bgrd,bsgd->bgrs", qf, keys)
    pos = jnp.arange(S, dtype=jnp.int32)
    s = jnp.where((pos[None, None, None, :] < cache_len[:, None, None, None]),
                  s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", pattn, vals)
    out = out.reshape(B, nh * hd).astype(xq.dtype)
    return out @ p["wo"]


def mla_dense_decode(p, xq, cfg, pool_layer, cache_len, positions):
    """Dense absorbed-MLA decode over the full latent pool slice."""
    B, S, _ = pool_layer.shape
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < cache_len[:, None]
    return mla_absorbed_decode(p, xq, cfg, pool_layer,
                               valid, positions)
