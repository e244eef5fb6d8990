"""Plain reference of a dense GQA decoder with the DSA lightning indexer.

Written from the configuration file alone; it imports nothing of the
program under test and takes nothing it made.  It computes in the
precision the configuration states (bfloat16 activations and weights,
matmuls accumulating in float32; float32 runs at the ``highest`` matmul
precision), over one token sequence at a time:

- the weights are drawn from the run's seed by the published recipe: one
  key per weight from ``split(PRNGKey(seed), n)`` in the order of
  :func:`leaf_specs`, normal with std ``scale / sqrt(fan_in)`` (fan-in is
  the second-to-last axis), rounded to bfloat16 (the type served);
- positions below the prompt length attend causally to every earlier
  position (prefill); each later position ``t`` attends to the top-k
  earlier positions by indexer score plus itself (sparse decode), where
  the score of position ``j`` is ``sum_h w_h relu(q_h . k_j) / sqrt(d_idx)``;
- RoPE rotates adjacent pairs of head dims, RMSNorm has eps 1e-6, the MLP
  is SwiGLU and the lm_head is untied.

The sequence given is what the served model saw: the prompt, the
prompt's last token again (the first decode input), then every served
token but the last.  The reference returns, for each decode position,
the gap between its best logit and the logit of the token that was
served there.

The control is the same computation in float8 (e4m3), the precision one
step below the bfloat16 the configuration states: every weight matrix
rounded with one scale per matrix, and every linear layer's activations
rounded with one scale per row, as an fp8 matmul takes them.  For each
decode position it reads the reference's gap of the token the control
puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
ROW_BLOCK = 256


def leaf_specs(m: Dict) -> List[Tuple[str, tuple, str, float]]:
    """(name, shape, init, scale) of every weight, in key-split order."""
    L, d, v, f = m["n_layers"], m["d_model"], m["vocab"], m["d_ff"]
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ni, di = m["n_idx_heads"], m["d_idx"]
    attn = []
    if m["qkv_bias"]:
        attn += [("bk", (L, nkv * hd), "zeros", 1.0),
                 ("bq", (L, nh * hd), "zeros", 1.0),
                 ("bv", (L, nkv * hd), "zeros", 1.0)]
    attn += [("wk", (L, d, nkv * hd), "normal", 1.0),
             ("wo", (L, nh * hd, d), "normal", 1.0),
             ("wq", (L, d, nh * hd), "normal", 1.0),
             ("wv", (L, d, nkv * hd), "normal", 1.0)]
    return ([("embed", (v, d), "normal", 1.0),
             ("final_norm", (d,), "ones", 1.0),
             ("lm_head", (d, v), "normal", 1.0)]
            + attn
            + [("w_w", (L, d, ni), "normal", 0.1),
               ("wk_idx", (L, d, di), "normal", 1.0),
               ("wq_idx", (L, d, ni * di), "normal", 1.0),
               ("ln1", (L, d), "ones", 1.0),
               ("ln2", (L, d), "ones", 1.0),
               ("w_down", (L, f, d), "normal", 1.0),
               ("w_gate", (L, d, f), "normal", 1.0),
               ("w_up", (L, d, f), "normal", 1.0)])


def make_weights(m: Dict, seed: int) -> Dict[str, jnp.ndarray]:
    """Every weight as served (bfloat16), drawn on the device in one call."""
    specs = leaf_specs(m)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for (name, shape, init, scale), k in zip(specs, keys):
            if init == "zeros":
                out[name] = jnp.zeros(shape, jnp.bfloat16)
            elif init == "ones":
                out[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                std = scale / np.sqrt(max(fan_in, 1))
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * std).astype(jnp.bfloat16)
        return out

    return draw(jax.random.PRNGKey(seed))


def fp8_weights(w: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """The control's weights: each matrix rounded to float8 e4m3 with one
    scale per matrix (per layer for stacked ones), held in bfloat16."""
    @jax.jit
    def quant(a):
        if a.ndim < 2:
            return a
        axes = tuple(range(a.ndim - 2, a.ndim))
        s = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=axes,
                    keepdims=True) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        q = (a.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
        return (q.astype(jnp.float32) * s).astype(jnp.bfloat16)
    return {k: quant(v) for k, v in w.items()}


def _fp8_rows(x):
    """``x`` rounded to float8 e4m3 with one scale per row, as an fp8
    matmul takes its activations."""
    x32 = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x32), -1, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * s).astype(x.dtype)


def _rms(x, g):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + 1e-6)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x [S, H, hd]; rotates adjacent pairs (2i, 2i+1)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1)
    return out.reshape(x.shape).astype(x.dtype)


def _attend(q, k, v, mask, n_rep):
    """q [R, nh, hd]; k, v [S, nkv, hd]; mask [R, S] -> [R, nh*hd]."""
    R, nh, hd = q.shape
    nkv = k.shape[1]
    qf = q.astype(jnp.float32).reshape(R, nkv, n_rep, hd) / np.sqrt(hd)
    s = jnp.einsum("rgqd,sgd->rgqs", qf, k.astype(jnp.float32))
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rgqs,sgd->rgqd", p, v.astype(jnp.float32))
    return o.reshape(R, nh * hd).astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=("m_items", "n_dec", "act", "fp8"))
def _hidden(w, tokens, n, *, m_items, n_dec, act, fp8=False):
    """Final hidden states of the ``n_dec`` rows from ``n`` on.
    tokens [S] (padded); n: prompt length.  With ``fp8`` every linear
    layer takes its activations rounded to float8 (the control)."""
    m = dict(m_items)
    S = tokens.shape[0]
    nh, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ni, di, topk = m["n_idx_heads"], m["d_idx"], m["topk"]
    n_rep = nh // nkv
    pos = jnp.arange(S, dtype=jnp.int32)
    x = jnp.take(w["embed"], tokens, axis=0).astype(act)
    drows = n + jnp.arange(n_dec, dtype=jnp.int32)
    n_blk = S // ROW_BLOCK

    def lin(a, wt):
        return (_fp8_rows(a) if fp8 else a) @ wt.astype(act)

    def layer(x, p):
        c = lambda a: a.astype(act)
        xn = _rms(x, p["ln1"])
        q = lin(xn, p["wq"])
        k = lin(xn, p["wk"])
        vv = lin(xn, p["wv"])
        if m["qkv_bias"]:
            q, k, vv = q + c(p["bq"]), k + c(p["bk"]), vv + c(p["bv"])
        q = _rope(q.reshape(S, nh, hd), pos, m["rope_theta"])
        k = _rope(k.reshape(S, nkv, hd), pos, m["rope_theta"])
        vv = vv.reshape(S, nkv, hd)
        ikeys = lin(xn, p["wk_idx"]).astype(jnp.float32)          # [S, di]

        # prefill rows: causal over every earlier position
        def causal(b):
            rows = b * ROW_BLOCK + jnp.arange(ROW_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q, b * ROW_BLOCK, ROW_BLOCK)
            return _attend(qb, k, vv, pos[None, :] <= rows[:, None], n_rep)
        out = jax.lax.map(causal, jnp.arange(n_blk)).reshape(S, nh * hd)

        # decode rows: the indexer's top-k earlier positions plus itself
        def sparse(rows):
            xd = jnp.take(xn, rows, axis=0, mode="clip")
            qi = lin(xd, p["wq_idx"]).astype(jnp.float32).reshape(-1, ni, di)
            wi = lin(xd, p["w_w"]).astype(jnp.float32)
            sc = jax.nn.relu(jnp.einsum("rhd,sd->rhs", qi, ikeys)) / np.sqrt(di)
            sc = jnp.einsum("rh,rhs->rs", wi, sc)
            sc = jnp.where(pos[None, :] < rows[:, None], sc, NEG_INF)
            top, idx = jax.lax.top_k(sc, min(topk, S))
            sel = jnp.zeros(sc.shape, bool).at[
                jnp.arange(rows.shape[0])[:, None], idx].set(top > NEG_INF / 2)
            sel = sel | (pos[None, :] == rows[:, None])
            return _attend(jnp.take(q, rows, axis=0, mode="clip"), k, vv, sel,
                           n_rep)
        od = jax.lax.map(sparse, drows.reshape(-1, ROW_BLOCK))
        out = out.at[drows].set(od.reshape(n_dec, nh * hd), mode="drop")
        x = x + lin(out, p["wo"]).astype(act)
        xn2 = _rms(x, p["ln2"])
        h = jax.nn.silu(lin(xn2, p["w_gate"])) * lin(xn2, p["w_up"])
        return x + lin(h, p["w_down"]).astype(act), None

    layer_keys = [k for k, *_ in leaf_specs(m)
                  if k not in ("embed", "final_norm", "lm_head")]
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in layer_keys})
    return _rms(jnp.take(x, drows, axis=0, mode="clip"), w["final_norm"])


@jax.jit
def _logits(h, lm_head):
    return h.astype(jnp.float32) @ lm_head.astype(jnp.float32)


def _gaps(logits, toks):
    best = logits.max(-1)
    return best - jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]


_gaps_jit = jax.jit(_gaps)


def sequence(prompt: np.ndarray, served: List[int], pad_to: int
             ) -> np.ndarray:
    """The token sequence the served model saw, padded to ``pad_to``."""
    seq = np.concatenate([prompt, prompt[-1:], np.asarray(served[:-1],
                                                          np.int32)])
    out = np.zeros(pad_to, np.int32)
    out[:len(seq)] = seq
    return out


def padded_len(max_ctx: int) -> int:
    return -(-max_ctx // ROW_BLOCK) * ROW_BLOCK


def compare(m: Dict, w: Dict, prompt: np.ndarray, served: List[int], *,
            max_ctx: int, n_dec: int, control_w: Dict = None,
            act: str = "float32") -> Dict[str, np.ndarray]:
    """Gaps of one request's served tokens (``gap``) and, with
    ``control_w``, of the tokens the control puts first (``control_gap``).
    ``n_dec`` is the static number of decode rows (>= len(served)).
    ``act`` is the activations' type: float32 (at the highest matmul
    precision) or bfloat16 (the default, accumulating in float32)."""
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
            ctok = jnp.argmax(_fp8_rows(hc) @ control_w["lm_head"], -1)
        out["control_gap"] = np.asarray(_gaps_jit(logits, ctok))[:k]
    return out
