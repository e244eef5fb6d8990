"""Fetch pipeline: speculative prefetch, radix/score warm-up, and the
issued/exposed fabric split (serving/prefetch.py + hisparse warm inserts).

Acceptance properties (ISSUE 2):
  - warm inserts never change results: decoded tokens are bit-identical
    with the pipeline on vs off (the pool stays authoritative);
  - ``issued_fabric_s >= exposed_fabric_s >= 0`` everywhere, and exposed
    is STRICTLY below issued on the CXL backend once overlap is on;
  - wasted-prefetch accounting is consistent: prefetched == useful +
    wasted, measured in-graph by the HiSparse pf_* counters;
  - on the shared drift trace of tests/test_engine_buffer.py, the
    engine-measured hit rate with prefetch + warm-up STRICTLY beats the
    LRU-only buffer;
  - the simulator's analytic overlap model (transfer.PipelineModel, the
    exact object simulate() uses) agrees with the engine-measured
    exposed time on the same trace.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from parity import assert_parity, build_engine, drift_parity, \
    drift_requests, run_to_completion

from repro.configs import get_config
from repro.core import hisparse
from repro.serving.engine import Engine
from repro.serving.prefetch import FetchPlanner, analytic_prefetch
from repro.serving.request import sharegpt_trace
from repro.serving.simulator import hit_rate


def _trace(cfg, n=4, ctx=40, out=6, seed=3):
    return sharegpt_trace(n, context_len=ctx, output_len=out, seed=seed,
                          ctx_jitter=0.0, vocab=cfg.vocab)


# ---------------------------------------------------------------------------
# warm_insert unit semantics
# ---------------------------------------------------------------------------


def test_warm_insert_is_insert_without_read():
    """Warm inserts make positions resident but count no hits/misses and
    advance no clock; a later demand read then hits."""
    B, S, buf, w = 1, 32, 8, 4
    state = hisparse.init_buffer(B, buf, S)
    idx = jnp.array([[3, 5, 7, 9]], jnp.int32)
    state2, ins = hisparse.warm_insert(state, idx, jnp.ones((B, w), bool))
    assert int(ins[0]) == w
    assert int(state2.pf_inserted[0]) == w and int(state2.pf_used[0]) == 0
    assert int(state2.clock[0]) == int(state.clock[0])
    _, hit = hisparse.lookup(state2, idx)
    assert bool(hit.all())
    # demand read: all four are hits, and all four consume their pf flag
    state3, hits, misses = hisparse.swap_in(state2, idx,
                                            jnp.ones((B, w), bool))
    assert int(hits[0]) == w and int(misses[0]) == 0
    assert int(state3.pf_used[0]) == w
    assert not bool(state3.pf_flag.any())        # flags consumed once


def test_warm_insert_never_evicts_current_step_hits():
    """A warm insert after a demand swap-in must evict older LRU slots,
    never the entries the current step just touched."""
    B, S, buf = 1, 64, 4
    state = hisparse.init_buffer(B, buf, S)

    def demand(state, positions):
        idx = jnp.array([positions], jnp.int32)
        return hisparse.swap_in(state, idx, jnp.ones_like(idx, bool))[0]

    state = demand(state, [0, 1])        # clock 1 (older)
    state = demand(state, [2, 3])        # clock 2: current step {2, 3}
    idx = jnp.array([[10, 11, 12]], jnp.int32)
    state, ins = hisparse.warm_insert(state, idx, jnp.ones_like(idx, bool))
    # only 2 evictable slots (0 and 1): the third candidate is dropped
    # rather than evicting the protected current-step entries
    assert int(ins[0]) == 2
    _, hit = hisparse.lookup(state, jnp.array([[2, 3]], jnp.int32))
    assert bool(hit.all())
    _, hit01 = hisparse.lookup(state, jnp.array([[0, 1]], jnp.int32))
    assert not bool(hit01.any())


def test_warm_insert_skips_resident_positions():
    B, S, buf = 1, 32, 8
    state = hisparse.init_buffer(B, buf, S)
    idx = jnp.array([[4, 5]], jnp.int32)
    state, ins = hisparse.warm_insert(state, idx, jnp.ones_like(idx, bool))
    assert int(ins[0]) == 2
    # same positions again: nothing inserted, counters unchanged
    state, ins2 = hisparse.warm_insert(state, idx, jnp.ones_like(idx, bool))
    assert int(ins2[0]) == 0
    assert int(state.pf_inserted[0]) == 2


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_property_warm_insert_preserves_read_values(data):
    """Interleaved warm inserts only change residency: a demand read's
    hits stay exactly its valid lanes resident before it (every lane
    reads the pool), the page table stays consistent, and pf accounting
    stays exact: used <= inserted and both monotone (wasted = inserted -
    used >= 0)."""
    B = data.draw(st.integers(1, 2))
    S = data.draw(st.sampled_from([16, 32]))
    buf = data.draw(st.sampled_from([4, 8]))
    k = data.draw(st.sampled_from([2, 4]))
    w = data.draw(st.sampled_from([1, 3]))
    state = hisparse.init_buffer(B, buf, S)
    rng = np.random.default_rng(data.draw(st.integers(0, 99)))
    for _ in range(data.draw(st.integers(1, 5))):
        idx = jnp.asarray(rng.integers(0, S, (B, k)), jnp.int32)
        valid = jnp.asarray(rng.random((B, k)) < 0.9)
        _, hit = hisparse.lookup(state, idx)
        resident = np.asarray(hit & valid).sum(1)
        state, hits, _ = hisparse.swap_in(state, idx, valid)
        np.testing.assert_array_equal(np.asarray(hits), resident)
        widx = jnp.asarray(rng.integers(0, S, (B, w)), jnp.int32)
        state, _ = hisparse.warm_insert(
            state, widx, jnp.asarray(rng.random((B, w)) < 0.9))
        ins = np.asarray(state.pf_inserted)
        used = np.asarray(state.pf_used)
        assert (used <= ins).all() and (used >= 0).all()
        # residency maps stay bijective under mixed demand/warm updates
        pt = np.asarray(state.page_table)
        sp = np.asarray(state.slot_pos)
        for b in range(B):
            for slot in range(buf):
                if sp[b, slot] >= 0:
                    assert pt[b, sp[b, slot]] == slot
            for pos in range(S):
                if pt[b, pos] >= 0:
                    assert sp[b, pt[b, pos]] == pos


# ---------------------------------------------------------------------------
# engine: bit-identity + accounting invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "minicpm-2b"])
def test_tokens_bit_identical_prefetch_on_off(arch):
    """The fetch pipeline changes traffic and timing, never results."""
    cfg = get_config(arch).reduced()
    engines = [Engine(cfg, slots=2, max_ctx=96, seed=2, prefetch=pf)
               for pf in (True, False)]
    for eng in engines:
        for r in _trace(cfg, n=2, ctx=40, out=50, seed=7):
            eng.submit(r)
        for _ in range(10):
            eng.step()
    on, off = engines
    assert on.slot_tokens == off.slot_tokens
    assert on.stats.prefetched_entries > 0


@settings(max_examples=3, deadline=None)
@given(st.data())
def test_property_prefetch_bit_identity_random_configs(data):
    """Random (arch, seed, trace) draws: greedy token streams match
    prefetch-on vs prefetch-off exactly."""
    arch = data.draw(st.sampled_from(["qwen2-1.5b", "gemma3-12b"]))
    seed = data.draw(st.integers(0, 5))
    tseed = data.draw(st.integers(0, 5))
    cfg = get_config(arch).reduced()
    streams = []
    for pf in (True, False):
        eng = Engine(cfg, slots=1, max_ctx=64, seed=seed, prefetch=pf)
        for r in _trace(cfg, n=1, ctx=24, out=20, seed=tseed):
            eng.submit(r)
        for _ in range(6):
            eng.step()
        streams.append([t[:] for t in eng.slot_tokens])
    assert streams[0] == streams[1]


def test_engine_accounting_invariants_with_prefetch():
    """issued >= exposed >= 0; prefetched == useful + wasted; prefetch
    entries are part of the unified entries_fetched tally."""
    cfg = get_config("qwen2-1.5b").reduced()
    eng = Engine(cfg, slots=2, max_ctx=96, prefetch=True)
    out = eng.run(_trace(cfg, n=4))
    assert out["n_done"] == 4
    s = eng.stats
    assert s.issued_fabric_s >= s.exposed_fabric_s >= 0.0
    assert s.exposed_fabric_s < s.issued_fabric_s     # CXL: overlap hides
    assert s.prefetched_entries == s.prefetch_useful + s.prefetch_wasted
    assert s.prefetch_useful > 0                      # speculation lands
    assert s.prefetch_wasted >= 0
    # unified schema: fabric entries = demand misses + prefetched
    assert s.pool_entries_fetched == s.buffer_misses + s.prefetched_entries
    assert s.traffic.prefetch_bytes > 0
    assert s.traffic.bytes_fetched >= s.traffic.prefetch_bytes


def test_engine_virtual_clock_is_deterministic():
    """Engine latency metrics come from the virtual clock (modeled
    compute + exposed fabric): two identical runs report identical
    TTFT/TBT, and timestamps are strictly positive/ordered."""
    cfg = get_config("qwen2-1.5b").reduced()
    outs = []
    for _ in range(2):
        eng = Engine(cfg, slots=2, max_ctx=96, seed=1)
        reqs = _trace(cfg, n=4)
        outs.append(eng.run(reqs))
        assert eng.clock_s > 0
        for r in reqs:
            assert 0 <= r.dispatch_s < r.first_token_s <= r.finish_s
    assert outs[0]["ttft_mean_s"] == outs[1]["ttft_mean_s"]
    assert outs[0]["tbt_mean_s"] == outs[1]["tbt_mean_s"]
    assert outs[0]["throughput_tok_s"] == outs[1]["throughput_tok_s"]


def test_warmup_plan_merges_scores_and_radix():
    cfg = get_config("qwen2-1.5b").reduced()
    planner = FetchPlanner(cfg, n_layers=2)
    warm = jnp.array([[1, 5, 9], [2, 6, 10]], jnp.int32)
    plan = planner.warmup_plan(warm, matched_tokens=4, prompt_len=40)
    assert plan is not None
    w_total = 3 + cfg.sac.warmup_radix
    assert plan.idx.shape == (2, w_total)
    assert bool(plan.valid[:, :3].all())
    # radix lanes: the 4 matched tail positions valid, earlier ones not
    radix_valid = np.asarray(plan.valid[:, 3:])
    assert radix_valid.sum(axis=1).tolist() == [4, 4]
    # no radix match, no scores -> no plan
    assert planner.warmup_plan(None, 0, 40) is None


def test_warmup_plan_masks_windowed_layers():
    """Radix warm-up lanes outside a windowed layer's decode mask are
    invalid — seeding them would be guaranteed waste."""
    cfg = get_config("gemma3-12b").reduced()   # kv layers: [local 32, global]
    planner = FetchPlanner(cfg, n_layers=2)
    assert planner.layer_windows == [cfg.local_window, 0]
    plan = planner.warmup_plan(None, matched_tokens=12, prompt_len=40)
    rv = np.asarray(plan.valid)
    r = cfg.sac.warmup_radix                   # prefix-tail positions 4..11
    # global layer keeps all tail lanes; the windowed layer only those
    # its decode mask (pos > prompt_len - window) can still select
    assert rv[1].sum() == r
    assert rv[0].sum() == sum(p > 40 - cfg.local_window
                              for p in range(12 - r, 12))
    assert 0 < rv[0].sum() < rv[1].sum()


def test_radix_warmup_seeds_shared_prefix():
    """Identical prompts through one slot: the recycled request's hot
    tier is pre-seeded from the radix-reused pages, so its cold-start
    misses drop vs the LRU-only engine."""
    cfg = get_config("qwen2-1.5b").reduced()
    runs = {}
    for pf in (False, True):
        eng = Engine(cfg, slots=1, max_ctx=96, seed=0, prefetch=pf)
        reqs = _trace(cfg, n=3, ctx=40, out=4)
        shared = reqs[0].prompt_tokens
        for r in reqs:
            r.prompt_tokens = shared.copy()
        out = eng.run(reqs)
        assert out["n_done"] == 3
        runs[pf] = eng.stats
    assert runs[True].buffer_misses < runs[False].buffer_misses
    assert runs[True].hit_rate > runs[False].hit_rate


# ---------------------------------------------------------------------------
# shared drift trace — now owned by the parity harness (tests/parity.py)
# ---------------------------------------------------------------------------


def test_drift_trace_prefetch_strictly_improves_hit_rate():
    """Acceptance: with prefetch + warm-up on, the engine-measured hit
    rate strictly beats the LRU-only buffer on the shared drift trace,
    and exposed < issued on the CXL backend."""
    for buf in (32, 64):
        runs = {}
        for pf in (False, True):
            eng = build_engine(buf, prefetch=pf)
            run_to_completion(eng, drift_requests(eng.cfg))
            runs[pf] = eng
        lru, pf = runs[False], runs[True]
        assert pf.stats.hit_rate > lru.stats.hit_rate, \
            (buf, pf.stats.hit_rate, lru.stats.hit_rate)
        assert pf.stats.buffer_misses < lru.stats.buffer_misses
        assert pf.stats.exposed_fabric_s < pf.stats.issued_fabric_s
        # speculation on this trace is near-perfect: most prefetched
        # entries are demand-hit the following step
        assert pf.stats.prefetch_precision > 0.5
        assert pf.stats.prefetched_entries == \
            pf.stats.prefetch_useful + pf.stats.prefetch_wasted


def test_sim_overlap_model_matches_engine_exposed():
    """Acceptance: the simulator's analytic overlap model — the exact
    PipelineModel simulate() evaluates — reproduces the engine-measured
    exposed seconds when driven by the engine's per-step issued traffic,
    and the hit-model-predicted issued total brackets the measured one.

    The measurement/replay loop and its tolerances now live in the
    parity harness (tests/parity.py assert_parity), shared with
    tests/test_engine_buffer.py and tests/test_parity_suite.py."""
    rep = drift_parity(32)
    assert_parity(rep)
    rep_pf = drift_parity(32, prefetch=True)
    assert_parity(rep_pf)
    # speculation issues extra fabric seconds on top of the LRU baseline
    assert rep_pf.measured_precision > 0.5


# ---------------------------------------------------------------------------
# analytic prefetch model (simulator side)
# ---------------------------------------------------------------------------


def test_analytic_prefetch_monotone_and_bounded():
    base = hit_rate(4096, 2048, 65536)
    h0, issued0 = analytic_prefetch(base, 0, 2048)
    assert h0 == base and issued0 == 0.0
    prev = base
    for w in (128, 512, 2048):
        h, issued = analytic_prefetch(base, w, 2048)
        assert base <= prev <= h <= 1.0
        assert issued > 0
        # consistency with the measured schema: the modeled useful
        # entries ((h - base) * topk) never exceed the modeled inserts
        assert (h - base) * 2048 <= issued + 1e-9
        prev = h


def test_simulator_prefetch_and_overlap_improve_cxl():
    from repro.serving.simulator import (SimConfig, default_backends,
                                         profile_from_config, simulate)
    model = profile_from_config(get_config("deepseek-v32"))
    b = default_backends()["cxl"]
    reqs = sharegpt_trace(48, context_len=65536, output_len=128, seed=1)
    base = simulate(reqs, model, b, SimConfig(concurrency=32))
    pipe = simulate(reqs, model, b, SimConfig(concurrency=32,
                                              overlap_frac=0.85,
                                              prefetch_width=512))
    assert base["n_done"] == pipe["n_done"] == 48
    # without an overlap model every issued second is exposed
    assert base["exposed_fabric_s"] == pytest.approx(
        base["issued_fabric_s"])
    assert pipe["exposed_fabric_s"] < pipe["issued_fabric_s"]
    assert pipe["sim_hit_rate"] > base["sim_hit_rate"]
    assert pipe["throughput_tok_s"] > base["throughput_tok_s"]
    # wasted-prefetch consistency holds for the analytic twin too:
    # prefetched >= useful >= 0 (wasted = prefetched - useful >= 0)
    assert pipe["prefetched_entries"] >= pipe["prefetch_useful"] >= 0
