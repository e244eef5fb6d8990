"""Decoder-only LM assembly for every assigned architecture.

A model is a list of **segments**, each scanned with ``lax.scan`` over
stacked parameters (keeping HLO size independent of depth):

  - ``dense``        one (attn + MLP) layer per iteration
  - ``moe``          one (attn + MoE) layer per iteration (dbrx / mixtral)
  - ``mla_dense``    one (MLA attn + MLP) layer: deepseek-v32's leading
                     ``first_k_dense`` layers, at the dense width
  - ``mla_moe``      one (MLA attn + MoE) layer: deepseek-v32's other
                     layers, each holding its ``n_experts_held`` experts
                     and the shared expert (models/moe.py)
  - ``lg_super``     gemma3 super-block: 5 local-window layers + 1 global
  - ``zamba_super``  zamba2 super-block: 6 Mamba2 layers + tied shared-attn
  - ``mamba_tail``   trailing plain Mamba2 layers (zamba2: 81 = 13*6 + 3)
  - ``xlstm_super``  xLSTM super-block: 3 mLSTM + 1 sLSTM

Three entry points per model (all pure functions of (params, state, in)):
  ``forward``  — full-sequence causal LM (training), dense attention;
  ``prefill``  — forward + emit the SAC pool (KV entries + indexer keys);
  ``decode``   — one token per request over the pool: indexer -> top-k ->
                 fetch (injected ``fetch_fn``: the SAC read path) -> sparse
                 attention -> write-back of the new entry.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hisparse
from repro.core import sac as sac_core
from repro.core.pool import FetchFn, local_fetch, pool_write
from repro.distributed.sharding import constrain
from repro.models import dsa, moe, ssm
from repro.models.layers import (DTYPE, ParamSpec, attn_param_specs,
                                 blocked_causal_attention,
                                 dense_attention_block, init_params,
                                 mlp_block, mlp_param_specs, rms_norm,
                                 spec_shapes)


# ---------------------------------------------------------------------------
# segment descriptors
# ---------------------------------------------------------------------------


_OPTS = threading.local()


def _opt(name: str, default=None):
    return getattr(_OPTS, "d", {}).get(name, default)


@contextlib.contextmanager
def _use_opts(d: Dict):
    old = getattr(_OPTS, "d", None)
    _OPTS.d = d or {}
    try:
        yield
    finally:
        _OPTS.d = old or {}


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str
    n: int                 # scan length
    kv_per_iter: int       # pool (attention) layers per iteration
    window: int = 0        # sliding window for this segment's attn layers


def build_segments(cfg: ModelConfig) -> List[Segment]:
    if cfg.xlstm:
        assert cfg.n_layers % 4 == 0, "xlstm stacks groups of 3 mLSTM + 1 sLSTM"
        return [Segment("xlstm_super", cfg.n_layers // 4, 0)]
    if cfg.ssm_state:  # zamba2 hybrid
        period = cfg.shared_attn_every
        n_super = cfg.n_layers // period
        tail = cfg.n_layers - n_super * period
        segs = [Segment("zamba_super", n_super, 1)]
        if tail:
            segs.append(Segment("mamba_tail", tail, 0))
        return segs
    if cfg.local_global_ratio:  # gemma3
        period = cfg.local_global_ratio + 1
        assert cfg.n_layers % period == 0
        return [Segment("lg_super", cfg.n_layers // period, period,
                        window=cfg.local_window)]
    if cfg.mla:
        if not cfg.n_experts:
            return [Segment("mla_dense", cfg.n_layers, 1)]
        k = cfg.first_k_dense
        return ([Segment("mla_dense", k, 1)] if k else []) + [
            Segment("mla_moe", cfg.n_layers - k, 1)]
    if cfg.n_experts:
        return [Segment("moe", cfg.n_layers, 1, window=cfg.sliding_window)]
    return [Segment("dense", cfg.n_layers, 1, window=cfg.sliding_window)]


def n_kv_layers(cfg: ModelConfig) -> int:
    return sum(s.n * s.kv_per_iter for s in build_segments(cfg))


def kv_layer_windows(cfg: ModelConfig) -> List[int]:
    """Sliding window per pool (attention) layer, in pool-layer order
    (0 = full attention).  Length == n_kv_layers(cfg); used by the fetch
    planner to avoid seeding windowed layers with positions their decode
    mask can never select."""
    wins: List[int] = []
    for seg in build_segments(cfg):
        if not seg.kv_per_iter:
            continue
        if seg.kind == "lg_super":
            per_iter = [cfg.local_window] * cfg.local_global_ratio + [0]
        else:
            per_iter = [seg.window] * seg.kv_per_iter
        wins.extend(per_iter * seg.n)
    return wins


def kv_entry_dim(cfg: ModelConfig) -> int:
    if not cfg.has_attention:
        return 0
    if cfg.mla:
        return cfg.kv_lora_rank + cfg.qk_rope_dim
    return dsa.gqa_entry_dim(cfg)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def _norm(cfg, name="g"):
    return ParamSpec((cfg.d_model,), ("G",), init="ones")


def _stack(specs, n: int):
    """Add a leading stacked-layer axis of size n to every ParamSpec leaf."""
    def one(s: ParamSpec):
        return ParamSpec((n, *s.shape), ("L", *s.dims), s.init, s.scale,
                         s.dtype)
    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _is_moe(seg: Segment, cfg: ModelConfig) -> bool:
    """Whether the segment's layers hold experts (an MLA model's leading
    dense layers do not)."""
    return bool(cfg.n_experts) and seg.kind != "mla_dense"


def _attn_layer_specs(cfg, moe_layer: Optional[bool] = None
                      ) -> Dict[str, Any]:
    if moe_layer is None:
        moe_layer = bool(cfg.n_experts)
    p: Dict[str, Any] = {"ln1": _norm(cfg), "ln2": _norm(cfg)}
    p["attn"] = (dsa.mla_param_specs(cfg) if cfg.mla
                 else attn_param_specs(cfg))
    if cfg.sac.enabled:
        p["idx"] = dsa.indexer_param_specs(cfg)
    p["mlp"] = (moe.moe_param_specs(cfg) if moe_layer
                else mlp_param_specs(cfg, cfg.d_ff_dense or cfg.d_ff))
    return p


def segment_specs(seg: Segment, cfg: ModelConfig):
    if seg.kind in ("dense", "moe", "mla_dense", "mla_moe"):
        return _stack(_attn_layer_specs(cfg, _is_moe(seg, cfg)), seg.n)
    if seg.kind == "lg_super":
        one = _attn_layer_specs(cfg)
        return _stack({"local": _stack(one, cfg.local_global_ratio),
                       "global": one}, seg.n)
    if seg.kind == "zamba_super":
        inner = {"ln": _norm(cfg), "mamba": ssm.mamba2_param_specs(cfg)}
        return _stack({"mamba_layers": _stack(inner, cfg.shared_attn_every)},
                      seg.n)
    if seg.kind == "mamba_tail":
        return _stack({"ln": _norm(cfg), "mamba": ssm.mamba2_param_specs(cfg)},
                      seg.n)
    if seg.kind == "xlstm_super":
        return _stack({"mlstm": _stack({"ln": _norm(cfg),
                                        **ssm.mlstm_param_specs(cfg)}, 3),
                       "slstm": {"ln": _norm(cfg),
                                 **ssm.slstm_param_specs(cfg)}}, seg.n)
    raise ValueError(seg.kind)


def model_param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab
    specs: Dict[str, Any] = {
        "embed": ParamSpec((v, d), ("V", "D"), scale=1.0),
        "segments": [segment_specs(s, cfg) for s in build_segments(cfg)],
        "final_norm": _norm(cfg),
        "lm_head": ParamSpec((d, v), ("D", "V")),
    }
    if cfg.ssm_state and cfg.shared_attn_every:
        # zamba2 tied shared-attention block (one set of weights, applied
        # after every 6th mamba layer)
        specs["shared"] = _attn_layer_specs(cfg)
    return specs


# ---------------------------------------------------------------------------
# forward (training) layer bodies
# ---------------------------------------------------------------------------


def _mlp_apply(p_mlp, x, cfg, *, decode: bool = False,
               moe_layer: Optional[bool] = None):
    """MLP or MoE on [B, S, D]; returns (out, aux, counts).

    ``counts`` ([B, S, 2] int32, or None) are the held-expert layer's
    routed assignments on held experts and those dropped
    (models/moe.py ``held_expert_block``).
    Grouped dispatch applies to full-sequence (train/prefill) calls only:
    decode steps route a handful of tokens — grouping them fragments the
    expert batches and regresses the collective term (§Perf B-series).
    """
    if moe_layer is None:
        moe_layer = bool(cfg.n_experts)
    if moe_layer and cfg.router == "noaux_tc":
        # its scopes (router, experts, shared_expert) name its parts
        out, counts = moe.held_expert_block(p_mlp, x, cfg)
        return out, jnp.float32(0), counts
    with jax.named_scope("mlp"):
        if moe_layer:
            groups = 1 if decode else _opt("moe_groups", 1)
            out, aux = moe.moe_block(p_mlp, x, cfg, groups=groups)
            return out, aux, None
        h = constrain(x @ p_mlp["w_gate"], ("B", "Sq", "F"))
        h = jax.nn.silu(h) * (x @ p_mlp["w_up"])
        out = h @ p_mlp["w_down"]
        return out, jnp.float32(0), None


def _attn_fwd(p, x, cfg, positions, window):
    """Shared attn sub-block on [B,S,D] -> (delta, entries, idx_keys,
    warm_idx).

    ``warm_idx`` ([B, w] int32, or None) is the layer's prefill warm-up
    candidate set when the ``warmup_w`` opt is on: the top-``w`` prompt
    positions by indexer score against the LAST prompt position's
    activations — the closest in-graph proxy for the first decode step's
    query, used by serving/prefetch.py to seed the HiSparse hot tier.
    """
    xn = rms_norm(x, p["ln1"])
    if cfg.mla:
        out, entry = dsa.mla_prefill_attention(p["attn"], xn, cfg, positions)
    else:
        out, (k, v) = dense_attention_block(p["attn"], xn, cfg, positions,
                                            window=window)
        entry = dsa.pack_kv_entry(k, v)
    idx_keys = (dsa.indexer_keys(p["idx"], xn, cfg, positions)
                if cfg.sac.enabled else None)
    warm = None
    w = _opt("warmup_w", 0)
    if w and cfg.sac.enabled:
        scores = dsa.indexer_scores(p["idx"], xn[:, -1], idx_keys, cfg,
                                    p_attn=p["attn"],
                                    positions=positions[:, -1])
        if window:
            # windowed layers only ever select from the trailing window
            # at decode time — seeding anything older is guaranteed waste
            S = scores.shape[-1]
            pos = jnp.arange(S, dtype=jnp.int32)
            scores = jnp.where(pos[None, :] > S - window, scores,
                               dsa.NEG_INF)
        ws, warm = jax.lax.top_k(scores, min(w, scores.shape[-1]))
        # masked-out lanes -> -1: the planner turns them into invalid
        # warm-insert lanes instead of seeding position 0 junk
        warm = jnp.where(ws > dsa.NEG_INF / 2, warm, -1).astype(jnp.int32)
    return out, entry, idx_keys, warm


def _layer_fwd(p, x, cfg, positions, window, moe_layer=None):
    """Full (attn + mlp) layer.  Returns (x', entry, idx_keys, warm, aux)."""
    delta, entry, idx_keys, warm = _attn_fwd(p, x, cfg, positions, window)
    x = constrain(x + delta, ("B", "S", "D"))
    out, aux, _ = _mlp_apply(p["mlp"], rms_norm(x, p["ln2"]), cfg,
                             moe_layer=moe_layer)
    x = constrain(x + out, ("B", "S", "D"))
    return x, entry, idx_keys, warm, aux


def _mamba_fwd(p, x, cfg):
    out, _ = ssm.mamba2_block(p["mamba"], rms_norm(x, p["ln"]), cfg,
                              chunk=_opt("ssm_chunk", 256))
    return constrain(x + out, ("B", "S", "D"))


def segment_fwd(seg: Segment, cfg: ModelConfig, shared_params=None,
                collect_entries: bool = True):
    """Build the scan body for a segment's full-sequence forward.

    Body: (x, p_slice, positions) -> (x', (entries, idx_keys), aux)
    entries: [kv_per_iter, B, S, d_kv] or None.
    """

    def stack_entries(es, ks, ws):
        if not collect_entries or not es:
            return None
        e = jnp.stack(es, 0)
        k = jnp.stack(ks, 0) if cfg.sac.enabled else jnp.zeros(())
        wm = (jnp.stack(ws, 0) if ws and ws[0] is not None
              else jnp.zeros(()))
        return (e, k, wm)

    if seg.kind in ("dense", "moe", "mla_dense", "mla_moe"):
        def body(x, p, positions):
            x, entry, ikeys, wm, aux = _layer_fwd(p, x, cfg, positions,
                                                  seg.window,
                                                  _is_moe(seg, cfg))
            return x, stack_entries([entry], [ikeys], [wm]), aux
        return body

    if seg.kind == "lg_super":
        def body(x, p, positions):
            es, ks, ws, aux = [], [], [], jnp.float32(0)
            for i in range(cfg.local_global_ratio):
                pl = jax.tree.map(lambda a: a[i], p["local"])
                x, e, kk, wm, a = _layer_fwd(pl, x, cfg, positions,
                                             cfg.local_window)
                es.append(e); ks.append(kk); ws.append(wm); aux += a
            x, e, kk, wm, a = _layer_fwd(p["global"], x, cfg, positions, 0)
            es.append(e); ks.append(kk); ws.append(wm); aux += a
            return x, stack_entries(es, ks, ws), aux
        return body

    if seg.kind == "zamba_super":
        def body(x, p, positions):
            for i in range(cfg.shared_attn_every):
                pl = jax.tree.map(lambda a: a[i], p["mamba_layers"])
                x = _mamba_fwd(pl, x, cfg)
            x, entry, ikeys, wm, aux = _layer_fwd(shared_params, x, cfg,
                                                  positions, 0)
            return x, stack_entries([entry], [ikeys], [wm]), aux
        return body

    if seg.kind == "mamba_tail":
        def body(x, p, positions):
            return _mamba_fwd(p, x, cfg), None, jnp.float32(0)
        return body

    if seg.kind == "xlstm_super":
        def body(x, p, positions):
            for i in range(3):
                pl = jax.tree.map(lambda a: a[i], p["mlstm"])
                x = x + ssm.mlstm_block(pl, rms_norm(x, pl["ln"]), cfg)
            ps = p["slstm"]
            x = x + ssm.slstm_block(ps, rms_norm(x, ps["ln"]), cfg)
            return constrain(x, ("B", "S", "D")), None, jnp.float32(0)
        return body

    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# decode layer bodies
# ---------------------------------------------------------------------------


def _attn_decode(p, x, cfg, ctx, kv_slice, idx_slice, window, hbuf=None):
    """One attention layer's decode.  x: [B, D]; kv_slice: [B, S, d].

    Returns (delta [B,D], new_entry [B,d_kv], new_idx_key [B,d_idx],
    new_hbuf, hits [B], misses [B]).  ``hbuf`` is this layer's HiSparse
    hot-tier state (core/hisparse.py) or None; the last three outputs are
    None unless a buffer was threaded in.
    """
    positions, cache_len = ctx["positions"], ctx["cache_len"]
    with jax.named_scope("attention"):
        xn = rms_norm(x, p["ln1"])
        if cfg.mla:
            own = dsa.mla_kv_entry(p["attn"], xn, cfg, positions)
        else:
            own = dsa.gqa_kv_entry(p["attn"], xn, cfg, positions)
    if ctx["mode"] == "dense" or not cfg.sac.enabled:
        with jax.named_scope("attention"):
            if window:
                delta = sac_core.window_attend(
                    p["attn"], xn, cfg, kv_slice, cache_len, positions, own,
                    window, fetch_fn=ctx["fetch_fn"])
            else:
                delta = sac_core.dense_attend(p["attn"], xn, cfg, kv_slice,
                                              cache_len, positions, own)
        new_key = jnp.zeros((x.shape[0], cfg.sac.d_idx), DTYPE)
        if hbuf is not None:   # keep scan pytree structure: untouched buffer
            zero = jnp.zeros((x.shape[0],), jnp.int32)
            return delta, own, new_key, hbuf, zero, zero
        return delta, own, new_key, None, None, None
    # SAC path: indexer -> top-k -> fetch -> sparse attention
    with jax.named_scope("indexer"):
        new_key = dsa.indexer_keys(p["idx"], xn, cfg, positions)
    if hbuf is None:
        delta = sac_core.sparse_attend(
            p["attn"], p["idx"], xn, cfg, kv_slice, idx_slice, cache_len,
            positions, own, fetch_fn=ctx["fetch_fn"],
            topk_fn=ctx.get("topk_fn"), window=window)
        return delta, own, new_key, None, None, None
    # buffered: the attended values are the pool fetch as above, and the
    # hot tier measures residency so the host charges only misses to the
    # fabric (paper §5.5);
    # prefetch_width > 0 additionally warm-inserts next-step speculation
    # into the hot tier (counted in the buffer's pf_* fields)
    delta, hbuf, hits, misses = sac_core.sparse_attend(
        p["attn"], p["idx"], xn, cfg, kv_slice, idx_slice, cache_len,
        positions, own, fetch_fn=ctx["fetch_fn"], topk_fn=ctx.get("topk_fn"),
        window=window, buf_state=hbuf,
        prefetch_width=ctx.get("prefetch_width", 0),
        prefetch_fn=ctx.get("prefetch_fn"),
        score_margin=ctx.get("score_margin", -1.0),
        pf_budget=ctx.get("pf_budget"))
    return delta, own, new_key, hbuf, hits, misses


def _layer_decode(p, x, cfg, ctx, kv_slice, idx_slice, window, hbuf=None,
                  moe_layer=None):
    """Returns (x', own, new_key, hbuf', hits, misses, counts): ``counts``
    ([B, 2] int32 or None) are the expert layer's (_mlp_apply)."""
    delta, own, new_key, hbuf2, hits, misses = _attn_decode(
        p, x, cfg, ctx, kv_slice, idx_slice, window, hbuf)
    x = x + delta
    out, _, counts = _mlp_apply(p["mlp"], rms_norm(x, p["ln2"])[:, None, :],
                                cfg, decode=True, moe_layer=moe_layer)
    x = x + out[:, 0]
    if counts is not None:
        counts = counts[:, 0]
    return (constrain(x, ("B", "D")), own, new_key, hbuf2, hits, misses,
            counts)


def _hb_layer(hb, i):
    """Slice layer ``i`` of an [a, ...]-stacked hot-buffer tree (or None)."""
    return None if hb is None else jax.tree.map(lambda t: t[i], hb)


def _hb_stack(hbs):
    """Stack per-layer hot-buffer states back to [a, ...] (or None)."""
    if not hbs or hbs[0] is None:
        return None
    return jax.tree.map(lambda *a: jnp.stack(a), *hbs)


def _hm_sum(hits, misses):
    """Stack per-layer hit/miss counts ([B] each) into ([a, B], [a, B]).

    Kept per-layer (not summed) so the host can measure per-layer miss
    rates — the signal the ``LayerSizer`` (serving/arbiter.py) apportions
    hot-tier slots by.  The decode assembly reduces over layers for the
    per-request ``buf_hits``/``buf_misses`` totals.
    """
    if not hits or hits[0] is None:
        return None
    return (jnp.stack(hits), jnp.stack(misses))


def segment_decode(seg: Segment, cfg: ModelConfig, shared_params=None):
    """Scan body for decode.

    (x, p_slice, kv_slices [a,B,S,d], idx_slices, hbuf_slices, rec_slice,
     ctx) -> (x', new_entries [a,B,d], new_keys [a,B,di], new_hbuf,
              (hits [B], misses [B]) | None, new_rec, moe_counts [B, 2] |
              None)

    ``hbuf_slices`` is the segment's per-iteration stack of HiSparse
    hot-buffer states ([a, ...] leading axes) or None; hit/miss counts
    are summed over the iteration's attention layers.
    """
    if seg.kind in ("dense", "moe", "mla_dense", "mla_moe"):
        def body(x, p, kv, ik, hb, rec, ctx):
            x, own, key, hb2, h, m, c = _layer_decode(
                p, x, cfg, ctx, kv[0], None if ik is None else ik[0],
                seg.window, _hb_layer(hb, 0), _is_moe(seg, cfg))
            return (x, own[None], key[None], _hb_stack([hb2]),
                    _hm_sum([h], [m]), rec, c)
        return body

    if seg.kind == "lg_super":
        def body(x, p, kv, ik, hb, rec, ctx):
            owns, keys, hbs, hs, ms = [], [], [], [], []
            for i in range(cfg.local_global_ratio):
                pl = jax.tree.map(lambda a: a[i], p["local"])
                x, own, key, hb2, h, m, _ = _layer_decode(
                    pl, x, cfg, ctx, kv[i], None if ik is None else ik[i],
                    cfg.local_window, _hb_layer(hb, i))
                owns.append(own); keys.append(key)
                hbs.append(hb2); hs.append(h); ms.append(m)
            g = cfg.local_global_ratio
            x, own, key, hb2, h, m, _ = _layer_decode(
                p["global"], x, cfg, ctx, kv[g],
                None if ik is None else ik[g], 0, _hb_layer(hb, g))
            owns.append(own); keys.append(key)
            hbs.append(hb2); hs.append(h); ms.append(m)
            return (x, jnp.stack(owns), jnp.stack(keys), _hb_stack(hbs),
                    _hm_sum(hs, ms), rec, None)
        return body

    if seg.kind == "zamba_super":
        def body(x, p, kv, ik, hb, rec, ctx):
            new_rec = []
            for i in range(cfg.shared_attn_every):
                pl = jax.tree.map(lambda a: a[i], p["mamba_layers"])
                st = jax.tree.map(lambda a: a[i], rec)
                out, st2 = ssm.mamba2_decode(pl["mamba"],
                                             rms_norm(x, pl["ln"]), cfg, st)
                x = x + out
                new_rec.append(st2)
            x, own, key, hb2, h, m, _ = _layer_decode(
                shared_params, x, cfg, ctx, kv[0],
                None if ik is None else ik[0], 0, _hb_layer(hb, 0))
            rec_out = jax.tree.map(lambda *a: jnp.stack(a), *new_rec)
            return (x, own[None], key[None], _hb_stack([hb2]),
                    _hm_sum([h], [m]), rec_out, None)
        return body

    if seg.kind == "mamba_tail":
        def body(x, p, kv, ik, hb, rec, ctx):
            out, rec2 = ssm.mamba2_decode(p["mamba"], rms_norm(x, p["ln"]),
                                          cfg, rec)
            return x + out, None, None, None, None, rec2, None
        return body

    if seg.kind == "xlstm_super":
        def body(x, p, kv, ik, hb, rec, ctx):
            m_rec, s_rec = rec
            new_m = []
            for i in range(3):
                pl = jax.tree.map(lambda a: a[i], p["mlstm"])
                st = jax.tree.map(lambda a: a[i], m_rec)
                out, st2 = ssm.mlstm_decode(pl, rms_norm(x, pl["ln"]), cfg, st)
                x = x + out
                new_m.append(st2)
            ps = p["slstm"]
            out, s2 = ssm.slstm_decode(ps, rms_norm(x, ps["ln"]), cfg, s_rec)
            x = x + out
            m_out = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
            return x, None, None, None, None, (m_out, s2), None
        return body

    raise ValueError(seg.kind)


# ---------------------------------------------------------------------------
# recurrent-state builders
# ---------------------------------------------------------------------------


def segment_rec_shapes(seg: Segment, cfg: ModelConfig, batch: int):
    """ShapeDtypeStructs of one scan-iteration's recurrent state."""
    if seg.kind == "zamba_super":
        (ssm_s, conv_s) = ssm.mamba2_state_shape(cfg, batch)
        a = cfg.shared_attn_every
        return (jax.ShapeDtypeStruct((a, *ssm_s), jnp.float32),
                jax.ShapeDtypeStruct((a, *conv_s), DTYPE))
    if seg.kind == "mamba_tail":
        (ssm_s, conv_s) = ssm.mamba2_state_shape(cfg, batch)
        return (jax.ShapeDtypeStruct(ssm_s, jnp.float32),
                jax.ShapeDtypeStruct(conv_s, DTYPE))
    if seg.kind == "xlstm_super":
        d, nh = cfg.d_model, cfg.n_heads
        hd = d // nh
        m = (jax.ShapeDtypeStruct((3, batch, nh, hd, hd), jnp.float32),
             jax.ShapeDtypeStruct((3, batch, nh, hd), jnp.float32),
             jax.ShapeDtypeStruct((3, batch, nh), jnp.float32))
        s = tuple(jax.ShapeDtypeStruct((batch, d), jnp.float32)
                  for _ in range(4))
        return (m, s)
    return None


def _stacked_rec_shapes(seg: Segment, cfg, batch):
    per = segment_rec_shapes(seg, cfg, batch)
    if per is None:
        return None
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((seg.n, *s.shape), s.dtype), per)


# ---------------------------------------------------------------------------
# the model facade
# ---------------------------------------------------------------------------


class TransformerLM:
    """build once per (cfg, fetch_fn, mode); all methods are pure."""

    def __init__(self, cfg: ModelConfig, fetch_fn: FetchFn = local_fetch,
                 mode: str = "sac", topk_fn: Optional[Callable] = None,
                 remat: bool = True, opts: Optional[Dict] = None):
        self.cfg = cfg
        self.fetch_fn = fetch_fn
        self.mode = mode if cfg.sac.enabled else "dense"
        self.topk_fn = topk_fn
        self.remat = remat
        self.opts = opts or {}
        self.segments = build_segments(cfg)
        self.specs = model_param_specs(cfg)
        self.n_kv = n_kv_layers(cfg)
        self.kv_dim = kv_entry_dim(cfg)
        # beyond-paper: fp8 pool storage halves pool HBM + fetch traffic.
        # The fetch psum is an exactly-one-owner reduction (masked zeros
        # elsewhere), so low-precision summation is bit-exact.
        self.kv_dtype = (jnp.float8_e4m3fn if cfg.sac.kv_quant == "fp8"
                         else DTYPE)

    # -- params ------------------------------------------------------------
    def init(self, key) -> Dict:
        return init_params(self.specs, key)

    def param_shapes(self):
        return spec_shapes(self.specs)

    # -- training forward ----------------------------------------------------
    def forward(self, params, tokens) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """tokens [B, S] -> (logits [B, S, V], aux_loss)."""
        with _use_opts(self.opts):
            return self._forward(params, tokens)

    def _forward(self, params, tokens):
        x, positions = self._embed_seq(params, tokens)
        aux_total = jnp.float32(0)
        for si, seg in enumerate(self.segments):
            body = segment_fwd(seg, self.cfg, params.get("shared"),
                               collect_entries=False)

            def scan_body(carry, p, _body=body):
                x, aux = carry
                x, _, a = _body(x, p, positions)
                return (x, aux + a), None

            if self.remat:
                scan_body = jax.checkpoint(scan_body)
            (x, aux_total), _ = jax.lax.scan(
                scan_body, (x, aux_total), params["segments"][si])
        return self._logits(params, x), aux_total

    # -- prefill -------------------------------------------------------------
    def prefill(self, params, tokens, lengths=None):
        """tokens [B, S] -> (serve_state, last_logits [B, V]).

        Writes every position's KV entry + indexer key into a fresh pool
        (the paper's prefill-instance write path).
        """
        with _use_opts(self.opts):
            return self._prefill(params, tokens, lengths)

    def _prefill(self, params, tokens, lengths=None):
        B, S = tokens.shape
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        x, positions = self._embed_seq(params, tokens)
        pools, ikeys, warms = [], [], []
        collect_warm = bool(self.opts.get("warmup_w")) and self.cfg.sac.enabled
        for si, seg in enumerate(self.segments):
            body = segment_fwd(seg, self.cfg, params.get("shared"),
                               collect_entries=True)

            def scan_body(x, p, _body=body):
                x, entries, _ = _body(x, p, positions)
                return x, entries

            x, entries = jax.lax.scan(scan_body, x, params["segments"][si])
            if entries is not None and seg.kv_per_iter:
                e, k, wm = entries
                # e: [n, a, B, S, d] -> [n*a, B, S, d]
                pools.append(e.reshape(-1, B, S, e.shape[-1]))
                if self.cfg.sac.enabled:
                    ikeys.append(k.reshape(-1, B, S, k.shape[-1]))
                if collect_warm:
                    warms.append(wm.reshape(-1, B, wm.shape[-1]))
        state = self._empty_state(B, S)
        if pools:
            state["kv_pool"] = constrain(
                jnp.concatenate(pools, 0).astype(self.kv_dtype),
                ("L", "B", "SP", "G"))
            if self.cfg.sac.enabled:
                state["idx_pool"] = constrain(
                    jnp.concatenate(ikeys, 0).astype(DTYPE),
                    ("L", "B", "SP", "G"))
            if warms:
                # per-layer top-scoring prompt positions [L, B, w]: the
                # prefill-time warm-up plan consumed by serving/prefetch.py
                # (popped by the engine — NOT part of the serve state)
                state["warm_idx"] = jnp.concatenate(warms, 0)
        state["cache_len"] = lengths
        # recurrent archs: replay the sequence through decode to build state
        # (prefill for SSMs is exercised via forward(); serving starts decode
        # from the scanned final states — built by running mamba/xlstm fwd
        # with state collection, omitted for pool archs.)
        last_idx = jnp.clip(lengths - 1, 0, S - 1)
        logits = self._logits(params, x)
        last = jnp.take_along_axis(
            logits, last_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return state, last

    # -- decode ----------------------------------------------------------------
    def decode(self, params, state, tokens, pf_budget=None):
        """One decode step.  tokens [B] -> (state', logits [B, V]).

        ``pf_budget`` ([B] int32 or None) is the step's arbiter-granted
        speculative width per request (serving/arbiter.py): it caps how
        many speculation lanes each request may warm-insert — traffic
        shaping only, decoded tokens never depend on it."""
        with _use_opts(self.opts):
            return self._decode(params, state, tokens, pf_budget)

    def _decode(self, params, state, tokens, pf_budget=None):
        cfg = self.cfg
        B = tokens.shape[0]
        x = jnp.take(params["embed"], tokens, axis=0).astype(DTYPE)
        x = constrain(x, ("B", "D"))
        cache_len = state["cache_len"]
        ctx = {
            "positions": cache_len,       # 0-indexed position of new token
            "cache_len": cache_len,
            "fetch_fn": self.fetch_fn,
            "topk_fn": self.topk_fn,
            "mode": self.mode,
            "prefetch_width": int(self.opts.get("prefetch_width", 0)),
            "prefetch_fn": self.opts.get("prefetch_fn"),
            "score_margin": float(self.opts.get("score_margin", -1.0)),
            "pf_budget": pf_budget,
        }
        kv_pool, idx_pool = state.get("kv_pool"), state.get("idx_pool")
        hot = state.get("hot_buf")    # layered hisparse.BufferState or None
        # speculative-prefetch step deltas: the pf_* counters inside the
        # buffer are cumulative, so the step's contribution is post - pre
        # The named scopes here and in the layer bodies label the ops by
        # part for the device trace; they change no op.
        with jax.named_scope("hot_tier"):
            pf_ins0 = hot.pf_inserted.sum(0) if hot is not None else None
            pf_use0 = hot.pf_used.sum(0) if hot is not None else None
        pool_closure = bool(self.opts.get("pool_closure"))
        use_idx = idx_pool is not None and self.mode == "sac"
        new_entries, new_keys = [], []
        hits_l, misses_l = [], []     # per-kv-layer [l, B] blocks, in order
        moe_counts = []               # per expert segment [B, 2]
        kv_off = 0
        for si, seg in enumerate(self.segments):
            body = segment_decode(seg, cfg, params.get("shared"))
            a = seg.kv_per_iter
            rec = state.get(f"rec_{si}")
            hb_g = None
            if hot is not None and a and kv_pool is not None:
                # this segment's hot-buffer layer block, regrouped to
                # [n, a, ...] so the scan threads one [a, ...] slice per
                # iteration (mutable xs/ys — unlike the read-only pools,
                # the buffer is UPDATED by every layer's swap_in)
                with jax.named_scope("hot_tier"):
                    hb_g = jax.tree.map(
                        lambda t: jax.lax.dynamic_slice_in_dim(
                            t, kv_off, seg.n * a, 0).reshape(
                                seg.n, a, *t.shape[1:]), hot)

            if pool_closure and a and kv_pool is not None:
                # §Perf C4: pools stay closure-captured, FLAT — each
                # iteration dynamic-slices its [a, B, S, d] layer block
                # straight out of the state buffer.  No grouped reshape
                # (which forced a layout-assignment copy of the whole
                # pool) and no scan-xs streaming (which double-buffers it).
                def scan_body(x, xs, _body=body, _off=kv_off, _a=a):
                    p, i, hb, rc = xs
                    with jax.named_scope("pool_slice"):
                        kv = jax.lax.dynamic_slice_in_dim(
                            kv_pool, _off + i * _a, _a, 0)
                        ik = (jax.lax.dynamic_slice_in_dim(
                            idx_pool, _off + i * _a, _a, 0) if use_idx
                            else None)
                    x, own, keys, hb2, hm, rc2, mc = _body(x, p, kv, ik, hb,
                                                           rc, ctx)
                    return x, (own, keys, hb2, hm, rc2, mc)

                xs = (params["segments"][si],
                      jnp.arange(seg.n, dtype=jnp.int32), hb_g, rec)
                seg_off, kv_off = kv_off, kv_off + seg.n * a
            else:
                if a and kv_pool is not None:
                    S = kv_pool.shape[2]
                    with jax.named_scope("pool_slice"):
                        kv_g = jax.lax.dynamic_slice_in_dim(
                            kv_pool, kv_off, seg.n * a, 0).reshape(
                                seg.n, a, B, S, kv_pool.shape[-1])
                        ik_g = None
                        if use_idx:
                            ik_g = jax.lax.dynamic_slice_in_dim(
                                idx_pool, kv_off, seg.n * a, 0).reshape(
                                    seg.n, a, B, S, idx_pool.shape[-1])
                    seg_off, kv_off = kv_off, kv_off + seg.n * a
                else:
                    kv_g, ik_g, seg_off = None, None, kv_off

                def scan_body(x, xs, _body=body):
                    p, kv, ik, hb, rc = xs
                    x, own, keys, hb2, hm, rc2, mc = _body(x, p, kv, ik, hb,
                                                           rc, ctx)
                    return x, (own, keys, hb2, hm, rc2, mc)

                xs = (params["segments"][si], kv_g, ik_g, hb_g, rec)
            # "layers": what the scan itself does (each layer's slice of
            # the stacked weights, pools and hot tier; stacking its outputs)
            with jax.named_scope("layers"):
                x, (own, keys, hb2, hm, rec2, mc) = jax.lax.scan(
                    scan_body, x, xs)
            if mc is not None:
                moe_counts.append(mc.sum(0))
            if own is not None:
                new_entries.append(own.reshape(-1, B, own.shape[-1]))
                new_keys.append(keys.reshape(-1, B, keys.shape[-1]))
            if hb2 is not None:
                # fold the segment's updated [n, a, ...] buffer block back
                # into the layered [L, ...] state
                with jax.named_scope("hot_tier"):
                    flat = jax.tree.map(
                        lambda t: t.reshape(t.shape[0] * t.shape[1],
                                            *t.shape[2:]), hb2)
                    hot = jax.tree.map(
                        lambda full, upd, _o=seg_off:
                            jax.lax.dynamic_update_slice_in_dim(
                                full, upd, _o, 0),
                        hot, flat)
            if hm is not None:
                # hm: ([n, a, B], [n, a, B]) — flatten to this segment's
                # kv layers in pool order
                with jax.named_scope("hot_tier"):
                    hits_l.append(hm[0].reshape(-1, B))
                    misses_l.append(hm[1].reshape(-1, B))
            if rec2 is not None:
                state = dict(state)
                state[f"rec_{si}"] = rec2
        state = dict(state)
        if new_entries and kv_pool is not None:
            state["kv_pool"] = pool_write(
                kv_pool, jnp.concatenate(new_entries, 0), cache_len)
            if idx_pool is not None:
                state["idx_pool"] = pool_write(
                    idx_pool, jnp.concatenate(new_keys, 0), cache_len)
        if hot is not None:
            state["hot_buf"] = hot
            # per-step measured hot-tier outcomes, per layer ([L, B]) and
            # summed; the engine charges miss-only fabric traffic from the
            # totals and feeds the per-layer miss rates to the LayerSizer
            with jax.named_scope("hot_tier"):
                hl = (jnp.concatenate(hits_l, 0) if hits_l
                      else jnp.zeros((self.n_kv, B), jnp.int32))
                ml = (jnp.concatenate(misses_l, 0) if misses_l
                      else jnp.zeros((self.n_kv, B), jnp.int32))
                state["buf_hits_l"] = hl
                state["buf_misses_l"] = ml
                state["buf_hits"] = hl.sum(0)
                state["buf_misses"] = ml.sum(0)
                state["pf_inserted"] = hot.pf_inserted.sum(0) - pf_ins0
                state["pf_useful"] = hot.pf_used.sum(0) - pf_use0
        if moe_counts:
            # per slot since its admission (the engine's splice zeroes a
            # slot's row), over the expert layers: routed assignments on
            # held experts, and those dropped
            state["moe_counts"] = state["moe_counts"] + sum(moe_counts)
        state["cache_len"] = cache_len + 1
        with jax.named_scope("lm_head"):
            x = rms_norm(x, params["final_norm"])
            logits = (x @ params["lm_head"]).astype(jnp.float32)
        return state, constrain(logits, ("B", "V"))

    # -- state builders ---------------------------------------------------------
    def _empty_state(self, batch: int, seq_len: int,
                     device_buffer=0, buffer_width=None) -> Dict:
        """``device_buffer`` is the hot-tier size per layer: one int
        (uniform) or a per-layer sequence (serving/arbiter.py LayerSizer
        apportioning, realized by hisparse DISABLED slot markers).
        ``buffer_width`` overrides the static allocation width (>= every
        per-layer size) — the headroom online re-sizing
        (hisparse.resize_layers) needs to grow layers later."""
        cfg = self.cfg
        buffered = (max(device_buffer) if isinstance(device_buffer,
                                                     (list, tuple))
                    else device_buffer)
        state: Dict[str, Any] = {"cache_len": jnp.zeros((batch,), jnp.int32)}
        if self.n_kv:
            state["kv_pool"] = jnp.zeros(
                (self.n_kv, batch, seq_len, self.kv_dim), self.kv_dtype)
            if cfg.sac.enabled:
                state["idx_pool"] = jnp.zeros(
                    (self.n_kv, batch, seq_len, cfg.sac.d_idx), DTYPE)
            if buffered and cfg.sac.enabled and self.mode == "sac":
                # HiSparse hot tier: per-(layer, request) residency; the
                # decode step swaps its top-k reads in and reports measured
                # per-request hit/miss counts in buf_hits/buf_misses.
                state["hot_buf"] = hisparse.init_layered_buffer(
                    self.n_kv, batch, device_buffer, seq_len,
                    buf_max=buffer_width)
                state["buf_hits"] = jnp.zeros((batch,), jnp.int32)
                state["buf_misses"] = jnp.zeros((batch,), jnp.int32)
                # per-layer split of the same counters (LayerSizer signal)
                state["buf_hits_l"] = jnp.zeros((self.n_kv, batch),
                                                jnp.int32)
                state["buf_misses_l"] = jnp.zeros((self.n_kv, batch),
                                                  jnp.int32)
                # per-step speculative-prefetch outcomes (fetch pipeline)
                state["pf_inserted"] = jnp.zeros((batch,), jnp.int32)
                state["pf_useful"] = jnp.zeros((batch,), jnp.int32)
        if any(_is_moe(s, cfg) for s in self.segments) \
                and cfg.router == "noaux_tc":
            state["moe_counts"] = jnp.zeros((batch, 2), jnp.int32)
        for si, seg in enumerate(self.segments):
            shapes = _stacked_rec_shapes(seg, cfg, batch)
            if shapes is not None:
                state[f"rec_{si}"] = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return state

    def serve_state_shapes(self, batch: int, seq_len: int,
                           device_buffer=0, buffer_width=None) -> Dict:
        """ShapeDtypeStruct pytree of the serve state (dry-run input specs).

        Traced abstractly (zero allocation) so dry-runs can lower against
        arbitrarily large states."""
        return jax.eval_shape(
            lambda: self._empty_state(batch, seq_len, device_buffer,
                                      buffer_width))

    def init_serve_state(self, batch: int, seq_len: int,
                         device_buffer=0, buffer_width=None) -> Dict:
        return self._empty_state(batch, seq_len, device_buffer,
                                 buffer_width)

    # -- shared pieces -----------------------------------------------------------
    def _embed_seq(self, params, tokens):
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(DTYPE)
        x = constrain(x, ("B", "S", "D"))
        return x, jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)

    def _logits(self, params, x):
        x = rms_norm(x, params["final_norm"])
        logits = (x @ params["lm_head"]).astype(jnp.float32)
        return constrain(logits, ("B", "S", "V"))
