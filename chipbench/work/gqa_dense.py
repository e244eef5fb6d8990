"""Required work of a decode step of a dense GQA model with the DSA indexer.

Counted from the configuration's shapes, never from what the program
executes, so a roofline share reads the same work whatever implements it.

Per occupied slot whose cache holds ``ctx`` earlier entries, per layer:

- FLOPs: the q/k/v/o projections, the indexer's projections and its
  scores over the ``ctx`` cached keys, attention (scores and weighted
  values) over ``min(topk, ctx) + 1`` entries, and the SwiGLU MLP; once per
  slot, the lm_head.  A multiply-add counts 2.
- Bytes: the indexer keys over the context and the selected top-k entries
  read, the new entry and key written.  Once per step: every weight
  matrix and norm of the layers and the lm_head read once, plus the
  embedding rows of the batch (the table itself is not read whole).

Not counted, because they are implementation and may go: the rewrite of
the whole pool, the hot tier and speculative prefetch.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def layer_weight_bytes(m: Dict) -> int:
    d, nh, nkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    ni, di = m["n_idx_heads"], m["d_idx"]
    n = (d * nh * hd + 2 * d * nkv * hd + nh * hd * d       # q, k, v, o
         + d * ni * di + d * di + d * ni                    # indexer
         + 3 * d * f + 2 * d)                               # mlp, 2 norms
    if m["qkv_bias"]:
        n += nh * hd + 2 * nkv * hd
    return n * BF16


def slot_layer_flops(m: Dict, ctx: int) -> int:
    d, nh, nkv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                         m["head_dim"], m["d_ff"])
    ni, di = m["n_idx_heads"], m["d_idx"]
    attended = min(m["topk"], ctx) + 1
    proj = 2 * d * (nh * hd + 2 * nkv * hd) + 2 * nh * hd * d
    indexer = 2 * d * (ni * di + di + ni) + 2 * ni * di * ctx + 2 * ni * ctx
    attn = 4 * nh * hd * attended
    mlp = 6 * d * f
    return proj + indexer + attn + mlp


def slot_layer_bytes(m: Dict, ctx: int) -> int:
    entry = 2 * m["n_kv_heads"] * m["head_dim"] * BF16
    key = m["d_idx"] * BF16
    return ctx * key + min(m["topk"], ctx) * entry + entry + key


def decode_step(m: Dict, contexts: Iterable[int]) -> Dict[str, int]:
    """Required ``flops`` and ``bytes`` of one decode step whose occupied
    slots hold ``contexts`` earlier entries each."""
    contexts = list(contexts)
    L, d, v = m["n_layers"], m["d_model"], m["vocab"]
    flops = sum(L * slot_layer_flops(m, c) + 2 * d * v for c in contexts)
    nbytes = (L * layer_weight_bytes(m) + (d * v + d) * BF16
              + len(contexts) * d * BF16
              + sum(L * slot_layer_bytes(m, c) for c in contexts))
    return {"flops": flops, "bytes": nbytes}
