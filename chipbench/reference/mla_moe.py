"""Plain reference of DeepSeek-V3.2's decoder at one chip's share.

Written from the configuration file alone (the published equations: the
DeepSeek-V3 technical report, arXiv:2412.19437, for MLA, the router and
YaRN; DeepSeek-V3.2's ``inference/model.py`` for the indexer); it imports
nothing of the program under test and takes nothing it made.  It computes
in the precision the configuration states (bfloat16 activations and
weights, matmuls accumulating in float32), over one token sequence at a
time, in blocks of rows so that it fits one chip beside nothing:

- the weights are drawn from the run's seed by the published recipe: one
  key per weight from ``split(PRNGKey(seed), n)`` in the order of the
  flattened tree of :func:`leaf_tree` (dict keys sorted), normal with std
  ``1 / sqrt(fan_in)`` (fan-in is the second-to-last axis), rounded to
  bfloat16; norm gains ones, the indexer key norm's bias and the router's
  ``e_score_correction_bias`` zeros.  The expert layers hold the share the
  configuration states (``n_experts_held`` experts from ``expert_offset``)
  and the vocabulary its slice (``vocab``);
- each layer: RMSNorm (eps 1e-6); MLA with a normed query latent, a normed
  KV latent, RoPE with YaRN's frequencies on the 64 rope dims (adjacent
  pairs) and the softmax scale 1/sqrt(192) times YaRN's mscale squared;
  positions below the prompt length attend causally to every earlier
  position (prefill), each later position ``t`` to the indexer's top-k
  earlier positions plus itself (sparse decode);
- the V3.2 indexer: query ``wq_b(q_latent)``, key ``LayerNorm(wk(x))``,
  RoPE on the first ``idx_rope_dim`` dims of both, head weights
  ``weights_proj(x) / sqrt(n_idx_heads)``, score of position ``j``
  ``sum_h w_h relu(q_h . k_j) / sqrt(d_idx)``;
- the first ``first_k_dense`` layers end in a SwiGLU MLP of width
  ``d_ff_dense``; the others in DeepSeek's expert layer: sigmoid scores
  over all ``n_experts``, the picks by the scores plus the bias within the
  ``n_limited_groups`` groups whose two best biased scores sum highest, the
  weights the picked unbiased scores normalised and times
  ``routed_scaling_factor``; the held experts' SwiGLU outputs by those
  weights (zero where a token did not pick one) plus the shared expert;
  what the experts held elsewhere would add is left out, as on the chip;
- the final RMSNorm and the untied head over the vocabulary slice.

The sequence given is what the served model saw (``gqa_dense.sequence``),
and the reference returns for each decode position the gap between its
best logit and the logit of the token served there.

The control is the same computation in float8 (e4m3): every weight
matrix held in e4m3 with one scale per matrix (per layer, and per expert),
and every linear layer's activations rounded with one scale per row.  For
each decode position it reads the reference's gap of the token the control
puts first.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gqa_dense import _fp8_rows, _gaps, sequence

NEG_INF = -1e30
ROW_BLOCK = 256     # decode rows are a multiple of this; sequences too
ATTN_ROWS = 64      # query rows attended at once (128 heads x 8192 keys)
FFN_ROWS = 1024     # rows through an MLP or the expert layer at once
F32 = jnp.float32


def _leaf(shape, init="normal", dtype=jnp.bfloat16):
    return (tuple(shape), init, dtype)


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _layers(m: Dict, n: int, moe: bool) -> Dict:
    d, nh, hd = m["d_model"], m["n_heads"], m["head_dim"]
    dc, dr, qr = m["kv_lora_rank"], m["qk_rope_dim"], m["q_lora_rank"]
    ni, di = m["n_idx_heads"], m["d_idx"]
    L = lambda *s: (n, *s)                                    # noqa: E731
    tree = {
        "attn": {"w_dq": _leaf(L(d, qr)), "q_norm_g": _leaf(L(qr), "ones"),
                 "w_uq": _leaf(L(qr, nh * (hd + dr))),
                 "w_dkv": _leaf(L(d, dc + dr)),
                 "kv_norm_g": _leaf(L(dc), "ones"),
                 "w_uk": _leaf(L(dc, nh * hd)), "w_uv": _leaf(L(dc, nh * hd)),
                 "wo": _leaf(L(nh * hd, d))},
        "idx": {"wq_b": _leaf(L(qr, ni * di)), "wk": _leaf(L(d, di)),
                "k_norm_g": _leaf(L(di), "ones"),
                "k_norm_b": _leaf(L(di), "zeros"),
                "weights_proj": _leaf(L(d, ni))},
        "ln1": _leaf(L(d), "ones"), "ln2": _leaf(L(d), "ones"),
    }
    if not moe:
        f = m["d_ff_dense"]
        tree["mlp"] = {"w_gate": _leaf(L(d, f)), "w_up": _leaf(L(d, f)),
                       "w_down": _leaf(L(f, d))}
        return tree
    f, e, h = m["d_ff"], m["n_experts"], m["n_experts_held"]
    fs = m["n_shared_experts"] * f
    tree["mlp"] = {"router": _leaf(L(d, e)),
                   "router_bias": _leaf(L(e), "zeros", F32),
                   "w_gate": _leaf(L(h, d, f)), "w_up": _leaf(L(h, d, f)),
                   "w_down": _leaf(L(h, f, d))}
    if fs:
        tree["mlp"]["shared"] = {"w_gate": _leaf(L(d, fs)),
                                 "w_up": _leaf(L(d, fs)),
                                 "w_down": _leaf(L(fs, d))}
    return tree


def leaf_tree(m: Dict) -> Dict:
    """(shape, init, dtype) of every weight, nested as served."""
    d, v, k = m["d_model"], m["vocab"], m["first_k_dense"]
    segs = ([_layers(m, k, False)] if k else []) + [
        _layers(m, m["n_layers"] - k, True)]
    return {"embed": _leaf((v, d)), "final_norm": _leaf((d,), "ones"),
            "lm_head": _leaf((d, v)), "segments": segs}


def make_weights(m: Dict, seed: int) -> Dict:
    """Every weight as served (bfloat16), drawn on the device in one call."""
    leaves, tree = jax.tree_util.tree_flatten(leaf_tree(m), is_leaf=_is_leaf)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for (shape, init, dtype), k in zip(leaves, keys):
            if init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            elif init == "ones":
                out.append(jnp.ones(shape, dtype))
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                std = 1.0 / np.sqrt(max(fan_in, 1))
                out.append((jax.random.normal(k, shape, F32) * std
                            ).astype(dtype))
        return out

    return jax.tree_util.tree_unflatten(tree, draw(jax.random.PRNGKey(seed)))


def fp8_weights(w: Dict) -> Dict:
    """The control's weights: every matrix (the drawn normal leaves) as a
    pair of its float8 e4m3 values and one float32 scale per matrix (per
    layer for stacked ones, per expert for the experts'); norms and the
    router bias as they are."""
    @jax.jit
    def quant(a):
        s = jnp.max(jnp.abs(a.astype(F32)), axis=(-2, -1),
                    keepdims=True) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        return (a.astype(F32) / s).astype(jnp.float8_e4m3fn), s

    def one(path, a):
        # a matrix has two axes past the layers' (norms and biases fewer)
        stacked = "segments" in jax.tree_util.keystr(path)
        return quant(a) if a.ndim - stacked >= 2 else a

    return jax.tree_util.tree_map_with_path(one, w)


def _mat(a):
    """A weight as bfloat16: the control's (e4m3, scale) pairs dequantised."""
    if isinstance(a, tuple):
        q, s = a
        return (q.astype(F32) * s).astype(jnp.bfloat16)
    return a


def _rms(x, g):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * g.astype(F32)).astype(x.dtype)


def _layer_norm(x, g, b):
    x32 = x.astype(F32)
    mu = x32.mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32 - mu), -1, keepdims=True) + 1e-6)
    return (y * g.astype(F32) + b.astype(F32)).astype(x.dtype)


def yarn_inv_freq(dim: int, m: Dict) -> np.ndarray:
    """YaRN (arXiv:2309.00071) as DeepSeek-V3 computes it: frequencies
    slower than ``beta_slow`` turns over the original context are divided
    by the factor, faster than ``beta_fast`` kept, a linear ramp between."""
    theta, factor = m["rope_theta"], m["rope_factor"]
    base = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if factor <= 1:
        return base.astype(np.float32)
    orig = m["rope_original_max_pos"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    lo = max(math.floor(dim_of(m["rope_beta_fast"])), 0)
    hi = min(math.ceil(dim_of(m["rope_beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    keep = 1 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return (base / factor * (1 - keep) + base * keep).astype(np.float32)


def softmax_scale(m: Dict) -> float:
    ms = m["rope_mscale_all_dim"]
    f = m["rope_factor"]
    k = 0.1 * ms * math.log(f) + 1.0 if (f > 1 and ms) else 1.0
    return k * k / math.sqrt(m["head_dim"] + m["qk_rope_dim"])


def _rope(x, pos, inv_freq):
    """x [S, ..., r]: rotates adjacent pairs (2i, 2i+1) at ``pos`` [S]."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2].astype(F32), x[..., 1::2].astype(F32)
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                     -1).reshape(x.shape).astype(x.dtype)


def _route(logits, bias, m):
    """DeepSeek's noaux_tc: [R, E] float32 logits -> [R, E] weights (zero
    where not picked)."""
    R, E = logits.shape
    G, K = m["n_expert_groups"], m["topk_experts"]
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias
    if G > 1:
        g = biased.reshape(R, G, E // G)
        best2 = jax.lax.top_k(g, 2)[0].sum(-1)                 # [R, G]
        _, keep = jax.lax.top_k(best2, m["n_limited_groups"])
        kept = jnp.zeros((R, G), bool).at[jnp.arange(R)[:, None], keep].set(
            True)
        biased = jnp.where(kept[..., None], g, -jnp.inf).reshape(R, E)
    _, picked = jax.lax.top_k(biased, K)
    mask = jnp.zeros((R, E), bool).at[jnp.arange(R)[:, None], picked].set(
        True)
    w = jnp.where(mask, scores, 0.0)
    if m["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w * m["routed_scaling_factor"]


@functools.partial(jax.jit,
                   static_argnames=("m_items", "n_dec", "act", "fp8"))
def _hidden(w, tokens, n, *, m_items, n_dec, act, fp8=False):
    """Final hidden states of the ``n_dec`` rows from ``n`` on.
    tokens [S] (padded); n: prompt length.  With ``fp8`` every linear
    layer takes its activations rounded to float8 (the control)."""
    m = dict(m_items)
    S = tokens.shape[0]
    nh, hd, dr, dc = (m["n_heads"], m["head_dim"], m["qk_rope_dim"],
                      m["kv_lora_rank"])
    ni, di, ri, topk = (m["n_idx_heads"], m["d_idx"], m["idx_rope_dim"],
                        m["topk"])
    lo, hi = m["expert_offset"], m["expert_offset"] + m["n_experts_held"]
    pos = jnp.arange(S, dtype=jnp.int32)
    freq_q, freq_i = yarn_inv_freq(dr, m), yarn_inv_freq(ri, m)
    scale = softmax_scale(m)
    drows = n + jnp.arange(n_dec, dtype=jnp.int32)

    def lin(a, wt):
        return (_fp8_rows(a) if fp8 else a) @ _mat(wt).astype(act)

    def rows_map(fn, a, r):
        """fn over row blocks of ``a`` [S, ...] of ``r`` rows."""
        out = jax.lax.map(fn, a.reshape(S // r, r, *a.shape[1:]))
        return out.reshape(S, *out.shape[2:])

    def layer(x, p, moe):
        a = p["attn"]
        xn = _rms(x, p["ln1"])
        ql = _rms(lin(xn, a["w_dq"]), a["q_norm_g"])
        q = lin(ql, a["w_uq"]).reshape(S, nh, hd + dr)
        q_nope, q_pe = q[..., :hd], _rope(q[..., hd:], pos, freq_q)
        kv = lin(xn, a["w_dkv"])
        c = _rms(kv[:, :dc], a["kv_norm_g"])
        k_pe = _rope(kv[:, dc:], pos, freq_q)                  # [S, dr]
        k_nope = lin(c, a["w_uk"]).reshape(S, nh, hd)
        v = lin(c, a["w_uv"]).reshape(S, nh, hd)
        ix = p["idx"]
        iq = lin(ql, ix["wq_b"]).reshape(S, ni, di)
        iq = jnp.concatenate([_rope(iq[..., :ri], pos, freq_i),
                              iq[..., ri:]], -1)
        ik = _layer_norm(lin(xn, ix["wk"]), ix["k_norm_g"], ix["k_norm_b"])
        ik = jnp.concatenate([_rope(ik[:, :ri], pos, freq_i), ik[:, ri:]],
                             -1).astype(F32)
        iw = lin(xn, ix["weights_proj"]).astype(F32) / np.sqrt(ni)

        def attend(rows, mask):
            """rows [R] attend to the positions of ``mask`` [R, S] through
            keys and values up-projected from the latent (prefill)."""
            qr = jnp.take(jnp.concatenate([q_nope, q_pe], -1), rows, 0,
                          mode="clip").astype(F32) * scale
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe[:, None], (S, nh, dr))], -1)
            s = jnp.einsum("rhd,shd->rhs", qr, k.astype(F32))
            s = jnp.where(mask[:, None, :], s, NEG_INF)
            e = jnp.exp(s - s.max(-1, keepdims=True))
            o = jnp.einsum("rhs,shd->rhd", e, v.astype(F32))
            o = o / e.sum(-1)[..., None]
            return o.reshape(rows.shape[0], nh * hd).astype(act)

        def attend_latent(rows, mask):
            """The same attention with the up-projections absorbed into the
            query and the output (DeepSeek-V3's ``absorb`` decode): scores
            and weights over the cached latents themselves, in float32."""
            qn = jnp.take(q_nope, rows, 0, mode="clip").astype(F32)
            qp = jnp.take(q_pe, rows, 0, mode="clip").astype(F32)
            w_uk = _mat(a["w_uk"]).astype(F32).reshape(dc, nh, hd)
            w_uv = _mat(a["w_uv"]).astype(F32).reshape(dc, nh, hd)
            q_lat = jnp.einsum("rhd,chd->rhc", qn, w_uk)
            s = (jnp.einsum("rhc,sc->rhs", q_lat, c.astype(F32))
                 + jnp.einsum("rhd,sd->rhs", qp, k_pe.astype(F32))) * scale
            s = jnp.where(mask[:, None, :], s, NEG_INF)
            o = jnp.einsum("rhs,sc->rhc", jax.nn.softmax(s, -1), c.astype(F32))
            o = jnp.einsum("rhc,chd->rhd", o, w_uv)
            return o.reshape(rows.shape[0], nh * hd).astype(act)

        # prefill rows: causal over every earlier position
        out = jax.lax.map(
            lambda rows: attend(rows, pos[None, :] <= rows[:, None]),
            pos.reshape(-1, ATTN_ROWS)).reshape(S, nh * hd)

        # decode rows: the indexer's top-k earlier positions plus itself
        def sparse(rows):
            qi = jnp.take(iq, rows, 0, mode="clip").astype(F32)
            wi = jnp.take(iw, rows, 0, mode="clip")
            sc = jax.nn.relu(jnp.einsum("rhd,sd->rhs", qi, ik)) / np.sqrt(di)
            sc = jnp.einsum("rh,rhs->rs", wi, sc)
            sc = jnp.where(pos[None, :] < rows[:, None], sc, NEG_INF)
            top, idx = jax.lax.top_k(sc, min(topk, S))
            sel = jnp.zeros(sc.shape, bool).at[
                jnp.arange(rows.shape[0])[:, None], idx].set(top > NEG_INF / 2)
            return attend_latent(rows, sel | (pos[None, :] == rows[:, None]))
        od = jax.lax.map(sparse, drows.reshape(-1, ATTN_ROWS))
        out = out.at[drows].set(od.reshape(n_dec, nh * hd), mode="drop")
        x = x + lin(out, a["wo"]).astype(act)
        mp = p["mlp"]

        def ffn(xb):
            xb = _rms(xb, p["ln2"])

            def swiglu(e):
                h = jax.nn.silu(lin(xb, e["w_gate"])) * lin(xb, e["w_up"])
                return lin(h, e["w_down"])
            if not moe:
                return swiglu(mp)
            logits = ((_fp8_rows(xb) if fp8 else xb).astype(F32)
                      @ _mat(mp["router"]).astype(F32))
            wts = _route(logits, mp["router_bias"], m)[:, lo:hi]
            y = jnp.zeros(xb.shape, F32)
            for e in range(hi - lo):
                ex = {k: (jax.tree_util.tree_map(lambda t: t[e], mp[k])
                          ) for k in ("w_gate", "w_up", "w_down")}
                y = y + wts[:, e:e + 1] * swiglu(ex).astype(F32)
            if "shared" in mp:
                y = y + swiglu(mp["shared"]).astype(F32)
            return y.astype(act)
        return x + rows_map(ffn, x, math.gcd(FFN_ROWS, S))

    x = jnp.take(_mat(w["embed"]), tokens, axis=0).astype(act)
    segs = w["segments"]
    for i, seg in enumerate(segs):
        moe = i == len(segs) - 1
        x, _ = jax.lax.scan(lambda x, p, _m=moe: (layer(x, p, _m), None),
                            x, seg)
    return _rms(jnp.take(x, drows, axis=0, mode="clip"), w["final_norm"])


@jax.jit
def _logits(h, lm_head):
    return h.astype(F32) @ lm_head.astype(F32)


_gaps_jit = jax.jit(_gaps)


def padded_len(max_ctx: int) -> int:
    return -(-max_ctx // ROW_BLOCK) * ROW_BLOCK


def compare(m: Dict, w: Dict, prompt: np.ndarray, served: List[int], *,
            max_ctx: int, n_dec: int, control_w: Dict = None,
            act: str = "bfloat16") -> Dict[str, np.ndarray]:
    """Gaps of one request's served tokens (``gap``) and, with
    ``control_w``, of the tokens the control puts first (``control_gap``).
    ``n_dec`` is the static number of decode rows (>= len(served))."""
    n, k = len(prompt), len(served)
    S = padded_len(max_ctx)
    n_dec = -(-n_dec // ROW_BLOCK) * ROW_BLOCK
    if not 0 < k <= n_dec or n + k > S:
        raise ValueError(f"{k} served tokens after a {n}-token prompt do "
                         f"not fit {n_dec} decode rows of {S}")
    toks = jnp.asarray(sequence(prompt, served, S))
    items = tuple(sorted(m.items()))
    served_a = np.zeros(n_dec, np.int32)
    served_a[:k] = served
    prec = "highest" if act == "float32" else "default"
    with jax.default_matmul_precision(prec):
        h = _hidden(w, toks, jnp.int32(n), m_items=items, n_dec=n_dec,
                    act=jnp.dtype(act))
    with jax.default_matmul_precision("highest"):
        logits = _logits(h, w["lm_head"])
        out = {"gap": np.asarray(_gaps_jit(logits, jnp.asarray(served_a)))[:k]}
    if control_w is not None:
        with jax.default_matmul_precision("default"):
            hc = _hidden(control_w, toks, jnp.int32(n), m_items=items,
                         n_dec=n_dec, act=jnp.bfloat16, fp8=True)
            ctok = jnp.argmax(_fp8_rows(hc) @ _mat(control_w["lm_head"]), -1)
        out["control_gap"] = np.asarray(_gaps_jit(logits, ctok))[:k]
    return out
