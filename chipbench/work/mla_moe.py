"""Required work of a decode step of DeepSeek-V3.2's share on one chip:
absorbed MLA with the V3.2 indexer, leading dense layers, and expert
layers that hold ``n_experts_held`` of the router's ``n_experts``.

Counted from the configuration's shapes, never from what the program
executes, so a roofline share reads the same work whatever implements it.
The program runs every held expert over every token of the batch (a
dropless layer that reads each held expert's weights every step); counting
that would make the least work the program's own, and a faster layer
could then read over 100 % of its roofline.  So the held experts count at
what routing requires under uniform routing of ``T`` tokens, ``K`` picks
each over ``E`` experts of which ``H`` are held: ``H (1 - (1 - K/E)^T)``
distinct held experts read, and ``T K H / E`` routed assignments computed.

Per occupied slot whose cache holds ``ctx`` earlier entries, per layer:

- FLOPs: the query latent and its up-projection, the KV latent, the
  absorption of the query into the latent, scores and weighted latents
  over ``min(topk, ctx) + 1`` entries, the value up-projection and the
  output projection; the indexer's query, key and head weights and its
  scores over the ``ctx`` cached keys; then the dense MLP, or the router,
  the shared expert and the routed assignments above.  Once per slot the
  lm_head.  A multiply-add counts 2.
- Bytes: the indexer keys over the context and the selected top-k latent
  entries read, the new entry and key written.  Once per step: every
  weight of the attention, the indexer, the norms and the dense MLPs, the
  router and the shared expert, the distinct held experts above, the
  lm_head, and the batch's embedding rows.

``moe_flops`` and ``moe_bytes`` are the expert layers' part of each
(router, experts, shared expert), the work of the decode's ``router``,
``experts`` and ``shared_expert`` scopes.

Not counted, because they are implementation and may go: the rewrite of
the whole pool and the hot tier.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


def attn_weight_bytes(m: Dict) -> int:
    d, nh, hd = m["d_model"], m["n_heads"], m["head_dim"]
    dc, dr, qr = m["kv_lora_rank"], m["qk_rope_dim"], m["q_lora_rank"]
    ni, di = m["n_idx_heads"], m["d_idx"]
    n = (d * qr + qr + qr * nh * (hd + dr) + d * (dc + dr) + dc
         + 2 * dc * nh * hd + nh * hd * d                  # MLA
         + qr * ni * di + d * di + 2 * di + d * ni         # indexer
         + 2 * d)                                          # 2 norms
    return n * BF16


def slot_attn_flops(m: Dict, ctx: int) -> int:
    d, nh, hd = m["d_model"], m["n_heads"], m["head_dim"]
    dc, dr, qr = m["kv_lora_rank"], m["qk_rope_dim"], m["q_lora_rank"]
    ni, di = m["n_idx_heads"], m["d_idx"]
    attended = min(m["topk"], ctx) + 1
    proj = 2 * (d * qr + qr * nh * (hd + dr) + d * (dc + dr)
                + nh * hd * dc + nh * dc * hd + nh * hd * d)
    attn = 2 * nh * (dc + dr) * attended + 2 * nh * dc * attended
    indexer = (2 * (qr * ni * di + d * di + d * ni)
               + 2 * ni * di * ctx + 2 * ni * ctx)
    return proj + attn + indexer


def slot_layer_bytes(m: Dict, ctx: int) -> int:
    entry = (m["kv_lora_rank"] + m["qk_rope_dim"]) * BF16
    key = m["d_idx"] * BF16
    return ctx * key + min(m["topk"], ctx) * entry + entry + key


def expert_layer(m: Dict, tokens: int) -> Dict[str, float]:
    """FLOPs and bytes of one expert layer for ``tokens`` tokens."""
    d, f, E = m["d_model"], m["d_ff"], m["n_experts"]
    K, H = m["topk_experts"], m["n_experts_held"]
    fs = m["n_shared_experts"] * f
    hit = H * (1 - (1 - K / E) ** tokens) if tokens else 0.0
    flops = tokens * (2 * d * E + 6 * d * fs + 6 * d * f * K * H / E)
    nbytes = (d * E + 3 * d * fs) * BF16 + E * 4 + hit * 3 * d * f * BF16
    return {"flops": flops, "bytes": nbytes}


def decode_step(m: Dict, contexts: Iterable[int]) -> Dict[str, float]:
    """Required ``flops`` and ``bytes`` of one decode step whose occupied
    slots hold ``contexts`` earlier entries each, and the expert layers'
    part of them (``moe_flops``, ``moe_bytes``)."""
    contexts = list(contexts)
    T = len(contexts)
    L, k = m["n_layers"], m["first_k_dense"]
    d, v, fd = m["d_model"], m["vocab"], m["d_ff_dense"]
    moe = expert_layer(m, T)
    moe_flops, moe_bytes = (L - k) * moe["flops"], (L - k) * moe["bytes"]
    flops = (sum(L * slot_attn_flops(m, c) + 2 * d * v for c in contexts)
             + T * k * 6 * d * fd + moe_flops)
    nbytes = (L * attn_weight_bytes(m) + k * 3 * d * fd * BF16
              + (d * v + d) * BF16 + T * d * BF16
              + sum(L * slot_layer_bytes(m, c) for c in contexts)
              + moe_bytes)
    return {"flops": flops, "bytes": nbytes, "moe_flops": moe_flops,
            "moe_bytes": moe_bytes}
