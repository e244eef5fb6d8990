"""Mixture-of-experts layers.

``router == "noaux_tc"`` (DeepSeek-V3/V3.2): :func:`held_expert_block`, an
expert layer told which routed experts it holds (``expert_offset`` and
``n_experts_held`` of the router's ``n_experts``), as one chip of an
expert-parallel deployment holds them.  It routes every token over all
experts with DeepSeek's router (sigmoid scores; the picks use the scores
plus ``e_score_correction_bias``, within the ``n_limited_groups`` of the
``n_expert_groups`` groups whose two best biased scores sum highest; the
weights are the picked unbiased scores, normalised and times
``routed_scaling_factor``), computes its held experts' part for the
tokens routed to them, and adds the shared experts' output for every
token.  It drops nothing: each held
expert runs over every token of the batch and the router's weight, zero
where the token did not pick it, selects its part.  What the experts held
elsewhere add is not computed here, and nothing stands in for it.

Otherwise, token-choice top-k MoE with capacity-based dispatch
(GShard-style), below.

FLOP-honest: expert compute is E x C x (3 d f) with C = topk*T/E*cap_factor,
not the E/topk-times-inflated dense-dispatch einsum.

``groups`` (§Perf iteration B1): with groups=1 the dispatch cumsum runs
over ALL tokens — a global scatter-add whose [E, C, D] buffer GSPMD can
only realize with an all-reduce over the batch shards (TB-scale traffic
per MoE train step).  With groups = number of batch shards, tokens are
dispatched within their own group ([G, E, C/G, D], G on the batch axes),
every scatter stays shard-local, and the only cross-shard traffic left is
the expert-weight gather (ZeRO) + output reduce.  Per-group capacity is
the standard deployment policy (each DP rank bounds its own expert load —
this is also what bounds straggler skew from hot experts).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.layers import ParamSpec


def moe_param_specs(cfg) -> Dict[str, ParamSpec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    if cfg.router == "noaux_tc":
        h, fs = cfg.experts_held, cfg.n_shared_experts * f
        specs = {
            "router": ParamSpec((d, e), ("G", "E")),
            "router_bias": ParamSpec((e,), ("E",), init="zeros",
                                     dtype=jnp.float32),
            "w_gate": ParamSpec((h, d, f), ("E", "DE", "F")),
            "w_up": ParamSpec((h, d, f), ("E", "DE", "F")),
            "w_down": ParamSpec((h, f, d), ("E", "F", "DE")),
        }
        if fs:
            specs["shared"] = {"w_gate": ParamSpec((d, fs), ("D", "F")),
                               "w_up": ParamSpec((d, fs), ("D", "F")),
                               "w_down": ParamSpec((fs, d), ("F", "D"))}
        return specs
    return {
        "router": ParamSpec((d, e), ("G", "E")),
        "w_gate": ParamSpec((e, d, f), ("E", "DE", "F")),
        "w_up": ParamSpec((e, d, f), ("E", "DE", "F")),
        "w_down": ParamSpec((e, f, d), ("E", "F", "DE")),
    }


def _dispatch_one(xt, probs, E: int, K: int, C: int):
    """Capacity dispatch for one token group.

    xt: [T, D]; probs: [T, E] -> (dispatched [E*C+1, D], slot [T*K],
    weight [T*K], aux)."""
    T, D = xt.shape
    gate_vals, expert_ids = jax.lax.top_k(probs, K)              # [T, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    # load-balancing aux loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,)).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    flat_e = expert_ids.reshape(-1)                              # [T*K]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)[
        jnp.arange(T * K), flat_e]
    keep = pos_in_e < C
    slot = jnp.where(keep, flat_e * C + pos_in_e, E * C)         # sentinel

    dispatched = jnp.zeros((E * C + 1, D), xt.dtype)
    dispatched = dispatched.at[slot].add(
        jnp.repeat(xt, K, axis=0) * keep[:, None].astype(xt.dtype))
    w = (gate_vals.reshape(-1) * keep.astype(jnp.float32))
    return dispatched, slot, w, aux


def moe_block(p, x, cfg, *, cap_factor: float = 1.25, groups: int = 1):
    """x: [B, S, D] -> ([B, S, D], aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.topk_experts
    T = B * S
    groups = max(1, min(groups, T))
    while T % groups:
        groups //= 2
    Tg = T // groups
    C = max(int(K * Tg * cap_factor / E), 1)

    xt = x.reshape(groups, Tg, D)
    xt = constrain(xt, ("B", "Sq", "G"))
    logits = (xt @ p["router"]).astype(jnp.float32)              # [G, Tg, E]
    probs = jax.nn.softmax(logits, axis=-1)

    dispatched, slot, w, aux = jax.vmap(
        lambda a, b: _dispatch_one(a, b, E, K, C))(xt, probs)
    ex = dispatched[:, : E * C].reshape(groups, E, C, D)
    ex = constrain(ex, ("B", "E", "K", "G"))

    h = jnp.einsum("gecd,edf->gecf", ex, p["w_gate"])
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", ex, p["w_up"])
    out_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])         # [G,E,C,D]
    out_e = constrain(out_e, ("B", "E", "K", "G"))

    flat_out = jnp.concatenate(
        [out_e.reshape(groups, E * C, D),
         jnp.zeros((groups, 1, D), out_e.dtype)], axis=1)
    gathered = jnp.take_along_axis(flat_out, slot[..., None], axis=1)
    combined = (gathered * w[..., None].astype(x.dtype)
                ).reshape(groups, Tg, K, D).sum(2)
    return combined.reshape(B, S, D), aux.mean()


def moe_decode(p, x, cfg, *, groups: int = 1):
    """Decode-time MoE for a single token per request (S=1)."""
    out, _ = moe_block(p, x[:, None, :], cfg, cap_factor=2.0, groups=groups)
    return out[:, 0, :]


# ---------------------------------------------------------------------------
# DeepSeek's router and the held-expert layer
# ---------------------------------------------------------------------------


def noaux_tc_route(logits, bias, cfg):
    """DeepSeek-V3's ``noaux_tc`` routing of tokens over every expert.

    logits: [T, E] float32 router logits; bias: [E] correction bias ->
    (experts [T, K] int32, weights [T, K] float32)."""
    T, E = logits.shape
    G, K = cfg.n_expert_groups, cfg.topk_experts
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias
    if G > 1:
        grouped = biased.reshape(T, G, E // G)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [T, G]
        _, keep = jax.lax.top_k(group_score, cfg.n_limited_groups)
        kept = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], keep].set(True)
        biased = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(T, E)
    _, experts = jax.lax.top_k(biased, K)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def held_expert_block(p, x, cfg):
    """x: [B, S, D] -> (out [B, S, D], counts [B, S, 2] int32).

    ``counts[..., 0]``: the token's routed assignments that land on a held
    expert; ``counts[..., 1]``: those of them no held expert computed (the
    layer is dropless, so this reads 0)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    held = cfg.expert_offset + jnp.arange(cfg.experts_held, dtype=jnp.int32)
    with jax.named_scope("router"):
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        experts, weights = noaux_tc_route(logits, p["router_bias"], cfg)
        hit = experts[:, :, None] == held                     # [T, K, h]
        # each held expert's weight for each token, 0 where not routed
        w_held = (hit * weights[..., None]).sum(1)            # [T, h]
        routed = hit.sum((1, 2))
        computed = (hit.any(1)).sum(-1)
    with jax.named_scope("experts"):
        g = jnp.einsum("td,edf->etf", xt, p["w_gate"])
        u = jnp.einsum("td,edf->etf", xt, p["w_up"])
        y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, p["w_down"])
        out = jnp.einsum("te,etd->td", w_held, y.astype(jnp.float32))
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            sp = p["shared"]
            h = jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_up"])
            out = out + (h @ sp["w_down"]).astype(jnp.float32)
    counts = jnp.stack([routed, routed - computed], -1).astype(jnp.int32)
    return out.astype(x.dtype).reshape(B, S, D), counts.reshape(B, S, 2)
