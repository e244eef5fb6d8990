"""DeepSeek-V3.2 at one chip's share, at tiny size on the CPU: the serving
engine against the plain reference (chipbench/reference/mla_moe.py), the
held-expert layer's share, DeepSeek's router, YaRN, a dropless decode,
and the qwen path's indexer and prefill attention left as they were."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import dsa, moe
from repro.models.layers import (blocked_causal_attention, init_params,
                                 yarn_freqs)
from repro.models.transformer import build_segments
from repro.serving.engine import Engine
from repro.serving.request import Request

F32 = jnp.float32


def tiny():
    """The registry's deepseek-v32 at its tiny size (1 dense + 2 expert
    layers, 2 of 4 experts held), top-k over every position."""
    cfg = get_config("deepseek-v32").reduced()
    return dataclasses.replace(cfg, sac=dataclasses.replace(cfg.sac,
                                                            topk=64))


def sizes_of(cfg) -> dict:
    """The reference's sizes: the configuration file's ``model`` keys."""
    keys = ("n_layers", "first_k_dense", "d_model", "n_heads",
            "kv_lora_rank", "q_lora_rank", "qk_rope_dim", "d_ff",
            "d_ff_dense", "n_experts", "expert_offset", "topk_experts",
            "n_shared_experts", "router", "n_expert_groups",
            "n_limited_groups",
            "norm_topk_prob", "routed_scaling_factor", "vocab",
            "rope_theta", "rope_factor", "rope_original_max_pos",
            "rope_beta_fast", "rope_beta_slow", "rope_mscale",
            "rope_mscale_all_dim", "idx_rope_dim")
    m = {k: getattr(cfg, k) for k in keys}
    m.update(head_dim=cfg.hd, n_experts_held=cfg.experts_held,
             topk=cfg.sac.topk, d_idx=cfg.sac.d_idx,
             n_idx_heads=cfg.sac.n_idx_heads)
    return m


def test_tiny_keeps_the_pattern():
    cfg = get_config("deepseek-v32").reduced()
    assert [(s.kind, s.n) for s in build_segments(cfg)] == [
        ("mla_dense", 1), ("mla_moe", 2)]
    assert cfg.experts_held < cfg.n_experts and cfg.n_expert_groups > 1
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
    per_group = cfg.n_experts // cfg.n_expert_groups
    assert len({e // per_group for e in held}) > 1
    full = get_config("deepseek-v32")
    assert [(s.kind, s.n) for s in build_segments(full)] == [
        ("mla_dense", 3), ("mla_moe", 58)]
    assert full.experts_held == 256


def test_engine_prefill_and_decode_match_the_reference():
    """Prefill then decode through the pool, the indexer and the hot tier
    (``Engine.step``), against the reference's full forward over the same
    tokens.  Both compute in bfloat16 by different roads (the program's
    decode attends the latents with the up-projections absorbed, its
    prefill up-projects them; its logits are rounded to bfloat16), so the
    logits agree to a few bfloat16 steps (0.0156 at 2-4, 0.03 at 4-8),
    and the token served is the reference's best wherever the two best
    lie further apart than that.  A wrong softmax scale, frequency, norm
    or route moves them by tenths to units."""
    from chipbench.reference import mla_moe as ref

    cfg = tiny()
    eng = Engine(cfg, slots=2, max_ctx=64, backend="cxl", mode="sac",
                 prefetch=True, radix=True, seed=11)
    seen = []
    decode = eng._decode

    def recording(params, state, tokens, *a):
        st, logits = decode(params, state, tokens, *a)
        seen.append(np.asarray(logits))
        return st, logits
    eng._decode = recording
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 40,
                                               dtype=np.int32)
    req = Request(0, 0.0, len(prompt), 12, prompt)
    eng.submit(req)
    while not eng.step():
        pass
    served = list(req.out_tokens)
    assert len(served) == 12
    got = np.stack([lg[0] for lg in seen])[:len(served)]   # the first slot

    m = sizes_of(cfg)
    w = ref.make_weights(m, 11)
    S = ref.padded_len(64)
    h = ref._hidden(w, jnp.asarray(ref.sequence(prompt, served, S)),
                    jnp.int32(len(prompt)), m_items=tuple(sorted(m.items())),
                    n_dec=256, act=jnp.dtype("bfloat16"))
    want = np.asarray(ref._logits(h, w["lm_head"]))[:len(served)]
    diff = np.abs(got - want)
    assert float(np.median(diff.max(-1))) < 0.1, diff.max(-1)
    assert float(diff.max()) < 0.25, diff.max(-1)
    assert float(np.median(diff)) < 0.02, np.median(diff)
    gap = want.max(-1) - want[np.arange(len(served)), served]
    assert float(gap.max()) < 0.05, gap
    # the expert layers' counters: assignments on held experts, none dropped
    assert eng.stats.moe_held_assignments > 0
    assert eng.stats.moe_dropped == 0


def moe_cfg(**kw):
    cfg = get_config("deepseek-v32").reduced()
    base = dict(n_experts=8, topk_experts=3, n_expert_groups=4,
                n_limited_groups=2, n_experts_held=8, expert_offset=0)
    base.update(kw)
    return dataclasses.replace(cfg, **base)


def f32_params(cfg, key):
    specs = moe.moe_param_specs(cfg)
    p = init_params(specs, key)
    p = jax.tree.map(lambda a: a.astype(F32), p)
    # a bias that moves the picks
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                               (cfg.n_experts,), F32)
    return p


def test_disjoint_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: four chips holding experts {0,1},
    {2,3}, {4,5}, {6,7} each compute their part; with the shared expert
    (which every chip computes alike) counted once, the parts add up to
    the layer that holds all eight."""
    full_cfg = moe_cfg()
    p = f32_params(full_cfg, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, full_cfg.d_model),
                          F32)
    whole, counts = moe.held_expert_block(p, x, full_cfg)
    assert int(counts[..., 0].sum()) == 3 * 5 * full_cfg.topk_experts
    parts, routed = [], 0
    for off in range(0, 8, 2):
        cfg = moe_cfg(n_experts_held=2, expert_offset=off)
        share = dict(p, **{k: p[k][off:off + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        out, c = moe.held_expert_block(share, x, cfg)
        parts.append(out)
        routed += int(c[..., 0].sum())
        assert int(c[..., 1].sum()) == 0
    sp = p["shared"]
    xt = x.reshape(-1, x.shape[-1])
    shared = ((jax.nn.silu(xt @ sp["w_gate"]) * (xt @ sp["w_up"]))
              @ sp["w_down"]).reshape(x.shape)
    total = sum(parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert routed == 3 * 5 * full_cfg.topk_experts


def numpy_noaux_tc(logits, bias, n_group, topk_group, k, scale):
    """DeepSeek-V3's noaux_tc gate written out one token at a time."""
    experts, weights = [], []
    for row in logits.astype(np.float64):
        scores = 1 / (1 + np.exp(-row))
        biased = scores + bias
        groups = biased.reshape(n_group, -1)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(1)
        keep = np.argsort(-group_score, kind="stable")[:topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[keep] = groups[keep]
        pick = np.argsort(-masked.reshape(-1), kind="stable")[:k]
        w = scores[pick] / scores[pick].sum() * scale
        experts.append(sorted(pick.tolist()))
        weights.append(dict(zip(pick.tolist(), w)))
    return experts, weights


def test_router_matches_a_plain_noaux_tc():
    cfg = moe_cfg(n_experts=16, topk_experts=4, n_expert_groups=4,
                  n_limited_groups=2, routed_scaling_factor=2.5)
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16), F32) * 2
    bias = jax.random.normal(jax.random.PRNGKey(1), (16,), F32) * 0.2
    ex, w = moe.noaux_tc_route(logits, bias, cfg)
    want_e, want_w = numpy_noaux_tc(np.asarray(logits), np.asarray(bias),
                                    4, 2, 4, 2.5)
    ex, w = np.asarray(ex), np.asarray(w)
    for t in range(64):
        assert sorted(ex[t].tolist()) == want_e[t]
        for e, v in zip(ex[t], w[t]):
            assert math.isclose(float(v), want_w[t][int(e)], rel_tol=1e-5)
        # within the two kept groups of four
        assert len({int(e) // 4 for e in ex[t]}) <= 2


def published_yarn(dim, theta, factor, original, beta_fast, beta_slow):
    """``precompute_freqs_cis``'s frequencies in DeepSeek-V3's
    inference/model.py, in float64."""
    def corr_dim(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freqs = 1.0 / (theta ** (np.arange(0, dim, 2) / dim))
    smooth = 1 - ramp
    return freqs / factor * (1 - smooth) + freqs * smooth


@pytest.mark.parametrize("dim", [64, 16])
def test_yarn_frequencies_and_mscale_match_the_published_formula(dim):
    cfg = get_config("deepseek-v32")
    got = yarn_freqs(dim, cfg.rope_theta, cfg.rope_factor,
                     cfg.rope_original_max_pos, cfg.rope_beta_fast,
                     cfg.rope_beta_slow)
    want = published_yarn(dim, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the slowest dims are slowed by the factor, the fastest kept
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(want[-1])
    mscale = 0.1 * math.log(40) + 1.0
    assert dsa.mla_softmax_scale(cfg) == pytest.approx(
        mscale * mscale / math.sqrt(128 + 64), rel=1e-12)
    plain = dataclasses.replace(cfg, rope_factor=1.0)
    assert dsa.rope_freqs_of(plain, dim) is None
    assert dsa.mla_softmax_scale(plain) == pytest.approx(1 / math.sqrt(192))


def test_a_batch_on_one_held_expert_equals_its_tokens_one_at_a_time():
    """Every token of a batch routed to held expert 1: the layer computes
    each of them (no capacity to overflow), as it would alone."""
    cfg = moe_cfg(n_experts_held=2, expert_offset=1, topk_experts=2,
                  n_expert_groups=1, n_limited_groups=1)
    p = f32_params(cfg, jax.random.PRNGKey(7))
    p = dict(p, router_bias=jnp.zeros((8,), F32).at[1].set(100.0))
    p = {**p, "w_gate": p["w_gate"][1:3], "w_up": p["w_up"][1:3],
         "w_down": p["w_down"][1:3]}
    x = jax.random.normal(jax.random.PRNGKey(8), (8, 1, cfg.d_model), F32)
    batch, counts = moe.held_expert_block(p, x, cfg)
    assert (np.asarray(counts[:, 0, 0]) >= 1).all()
    assert int(counts[..., 1].sum()) == 0
    for t in range(8):
        alone, _ = moe.held_expert_block(p, x[t:t + 1], cfg)
        np.testing.assert_allclose(np.asarray(alone[0]),
                                   np.asarray(batch[t]), rtol=1e-6,
                                   atol=1e-6)
    capacity = max(int(cfg.topk_experts * 8 * 2.0 / cfg.n_experts), 1)
    assert capacity < 8      # the capacity path would drop some of them


def test_qwen_indexer_is_unchanged():
    """The GQA configurations keep the DSA indexer as it was: keys
    ``x @ wk_idx`` and scores ``sum_h w_h relu(q_h . k) / sqrt(d_idx)``,
    bit for bit."""
    cfg = get_config("qwen2-1.5b").reduced()
    p = init_params(dsa.indexer_param_specs(cfg), jax.random.PRNGKey(0))
    assert set(p) == {"wq_idx", "wk_idx", "w_w"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model),
                          jnp.bfloat16)
    keys = dsa.indexer_keys(p, x, cfg, jnp.arange(9)[None].repeat(2, 0))
    assert (np.asarray(keys) == np.asarray(x @ p["wk_idx"])).all()
    ni, di = cfg.sac.n_idx_heads, cfg.sac.d_idx
    xq = x[:, -1]
    q = (xq @ p["wq_idx"]).reshape(2, ni, di).astype(F32)
    w = (xq @ p["w_w"]).astype(F32)
    lg = jax.nn.relu(jnp.einsum("bhd,bsd->bhs", q, keys.astype(F32)))
    want = jnp.einsum("bh,bhs->bs", w, lg / np.sqrt(di))
    got = dsa.indexer_scores(p, xq, keys, cfg)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_head_blocked_prefill_attention_equals_the_whole():
    """Heads are independent: attending them in groups gives each head the
    result the whole gives, bit for bit; without ``head_block`` (every
    GQA prefill) the function is the one it was."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (1, 48, 8, 24), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 48, 8, 24), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 48, 8, 16), jnp.bfloat16)
    whole = blocked_causal_attention(q, k, v, chunk=16, scale=0.3)
    grouped = blocked_causal_attention(q, k, v, chunk=16, scale=0.3,
                                       head_block=2)
    assert grouped.shape == (1, 48, 8, 16)
    assert (np.asarray(whole) == np.asarray(grouped)).all()


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-v32"])
def test_expert_counters_cost_no_read_a_step(arch):
    """A decode step reads the same device values with or without an
    expert layer (the token, the cache lengths, the hot tier's and
    speculation's six counters); the expert counters are read once, when
    a request finishes, and only where the state holds them."""
    cfg = get_config(arch).reduced()
    eng = Engine(cfg, slots=2, max_ctx=64, prefetch=True, seed=0)
    assert ("moe_counts" in eng.state) == (arch == "deepseek-v32")
    prompt = np.arange(24, dtype=np.int32) % cfg.vocab
    req = Request(0, 0.0, len(prompt), 4, prompt)
    eng.submit(req)
    eng.step()
    reads = eng.stats.device_reads
    eng.step()
    assert eng.stats.device_reads - reads == 8
    assert eng.stats.moe_held_assignments == eng.stats.moe_dropped == 0
    while not eng.step():
        pass
    if arch == "deepseek-v32":
        # 4 tokens through 2 expert layers, 2 picks each, 2 of 4 held
        assert 0 < eng.stats.moe_held_assignments <= 4 * 2 * 2
    else:
        assert eng.stats.moe_held_assignments == 0
    assert eng.stats.moe_dropped == 0
