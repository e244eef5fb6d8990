"""Real HiSparse hot buffer wired into the engine decode path.

Acceptance properties (paper §5.5 miss-only traffic):
  - measured buffer_hits/buffer_misses are live, nonzero numbers;
  - fabric time is charged on misses only (less than the cold-read
    convention's full top-k charge);
  - decoded tokens are bit-identical with the buffer on vs off (the hot
    tier changes traffic, never results);
  - parity: the simulator's analytic hit_rate() matches the
    engine-measured hit rate on a shared drifting-top-k trace.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest
from parity import assert_parity, drift_parity

from repro.configs import get_config
from repro.serving.engine import Engine
from repro.serving.request import Request, sharegpt_trace


def _trace(cfg, n=4, ctx=40, out=6, seed=3):
    return sharegpt_trace(n, context_len=ctx, output_len=out, seed=seed,
                          ctx_jitter=0.0, vocab=cfg.vocab)


def test_buffer_counters_are_live():
    cfg = get_config("qwen2-1.5b").reduced()
    eng = Engine(cfg, slots=2, max_ctx=96)      # buffer on by default
    out = eng.run(_trace(cfg, n=4))
    assert out["n_done"] == 4
    assert eng.stats.buffer_hits + eng.stats.buffer_misses > 0
    assert eng.stats.buffer_hits > 0            # top-k sets overlap
    assert 0.0 < eng.stats.hit_rate < 1.0
    # pool traffic is THE miss traffic: entries fetched == misses, and
    # bytes follow at entry granularity
    assert eng.stats.pool_entries_fetched == eng.stats.buffer_misses
    assert eng.stats.traffic.bytes_fetched == \
        eng.stats.buffer_misses * eng.sac.entry_bytes


def test_fabric_charged_on_misses_only():
    cfg = get_config("qwen2-1.5b").reduced()
    on = Engine(cfg, slots=2, max_ctx=96, seed=1)
    off = Engine(cfg, slots=2, max_ctx=96, seed=1, track_buffer=False)
    r_on = on.run(_trace(cfg, n=4))
    r_off = off.run(_trace(cfg, n=4))
    assert off.stats.buffer_hits == off.stats.buffer_misses == 0
    # buffered engine fetched strictly fewer entries over the fabric
    assert on.stats.pool_entries_fetched < off.stats.pool_entries_fetched
    assert r_on["fabric_time_s"] < r_off["fabric_time_s"]
    # both decoded the same number of tokens
    assert r_on["engine_tokens"] == r_off["engine_tokens"]


def test_tokens_bit_identical_buffer_on_off():
    """The hot tier changes traffic, never results: greedy streams match
    token-for-token."""
    cfg = get_config("minicpm-2b").reduced()
    engines = [Engine(cfg, slots=2, max_ctx=96, seed=2,
                      track_buffer=tb) for tb in (True, False)]
    for eng in engines:
        # long outputs: no slot finishes within the observed window, so
        # slot_tokens holds every decoded token
        for r in _trace(cfg, n=2, ctx=40, out=50, seed=7):
            eng.submit(r)
        for _ in range(12):
            eng.step()
    on, off = engines
    assert on.slot_tokens == off.slot_tokens
    assert on.stats.buffer_hits + on.stats.buffer_misses > 0


def test_slot_recycling_resets_buffer_lane():
    """Three requests through one slot: the recycled lane must start cold
    (no cross-request residency) and still complete correctly."""
    cfg = get_config("qwen2-1.5b").reduced()
    eng = Engine(cfg, slots=1, max_ctx=96, seed=0)
    out = eng.run(_trace(cfg, n=3, ctx=24, out=4))
    assert out["n_done"] == 3
    # every request's first decode step starts cold: >= one full-topk miss
    # burst per request
    assert eng.stats.buffer_misses >= 3 * min(cfg.sac.topk, 24)


def test_engine_hit_rate_parity_with_analytic_model():
    """Ground the simulator's analytic hit model against the ENGINE's
    measured hit rate on a shared trace.

    The analytic model assumes the paper-scale workload: consecutive
    top-k sets drift slowly.  Tiny reduced models churn far more (random
    init indexer over a tiny candidate pool), so the shared trace is the
    controlled drift of the parity harness (tests/parity.py) injected
    via the engine's topk_fn hook — the read path, buffer updates, and
    counters are the real jitted wiring."""
    for buf in (32, 64):
        assert_parity(drift_parity(buf))


def test_per_layer_buffer_sizing_is_transparent():
    """LayerSizer apportioning (serving/arbiter.py): a windowed arch gets
    non-uniform per-layer sizes summing to the uniform total, decoded
    tokens stay bit-identical, and the per-layer miss counters are live
    so the sizer's miss-rate signal exists."""
    # kv layers: [local (window 8), global] — the window is shrunk below
    # the uniform per-layer size so apportioning has room to act
    cfg = dataclasses.replace(get_config("gemma3-12b").reduced(),
                              local_window=8)
    engines = {}
    for sizing in ("uniform", "windowed"):
        eng = Engine(cfg, slots=1, max_ctx=96, seed=2, layer_sizing=sizing)
        for r in _trace(cfg, n=1, ctx=40, out=30, seed=7):
            eng.submit(r)
        for _ in range(8):
            eng.step()
        engines[sizing] = eng
    uni, win = engines["uniform"], engines["windowed"]
    assert uni.buffer_sizes is None
    assert win.buffer_sizes is not None
    buf = cfg.sac.device_buffer_size
    assert sum(win.buffer_sizes) == buf * 2
    # the windowed layer is capped at its selectable window; the surplus
    # went to the full-attention layer
    assert win.buffer_sizes[0] <= cfg.local_window
    assert win.buffer_sizes[1] > buf
    # sizing shapes traffic, never results
    assert uni.slot_tokens == win.slot_tokens
    # ... and the reapportioned tier never hits less: the windowed layer
    # cannot use slots beyond its window, the global layer can
    assert win.stats.hit_rate >= uni.stats.hit_rate
    # per-layer counters are live and consistent with the totals
    for eng in engines.values():
        tot = eng.stats.layer_hits + eng.stats.layer_misses
        assert tot.sum() == eng.stats.buffer_hits + eng.stats.buffer_misses
        assert (eng.stats.layer_miss_rates() >= 0).all()


# ---------------------------------------------------------------------------
# exactness against recorded counters: the hot tier's residency, LRU order,
# prefetch flags and the tokens, step by step
# ---------------------------------------------------------------------------

EXACT_DATA = Path(__file__).parent / "data" / "engine_counters.json"
# case -> (max_ctx, SACConfig changes).  "bench" is the benchmark's CPU
# rehearsal build (chipbench/run.py --reduced): reduced qwen2-1.5b with
# top-k 64 over 2 slots x 64, prefetch and radix on.  "resize" keeps the
# reduced top-k of 16, so speculation lanes reach past the demand set, and
# re-sizes the layers online every 4 steps.
EXACT_CASES = {"bench": (64, {"topk": 64}),
               "resize": (96, {"resize_interval": 4})}
# (prompt tokens, output tokens) of the rehearsal mix, twice; the fourth
# prompt repeats the first one's leading 16 tokens (a radix match that
# seeds the warm-up)
EXACT_REQUESTS = [(24, 16), (40, 12), (32, 6), (24, 16), (40, 12), (32, 6)]


def _exact_run(case: str) -> dict:
    cfg = get_config("qwen2-1.5b").reduced()
    max_ctx, sac = EXACT_CASES[case]
    cfg = dataclasses.replace(cfg, sac=dataclasses.replace(cfg.sac, **sac))
    eng = Engine(cfg, slots=2, max_ctx=max_ctx, backend="cxl", mode="sac",
                 prefetch=True, radix=True, seed=5)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n, dtype=np.int32)
               for n, _ in EXACT_REQUESTS]
    prompts[3][:16] = prompts[0][:16]
    for rid, (p, (_, out)) in enumerate(zip(prompts, EXACT_REQUESTS)):
        eng.submit(Request(rid, 0.0, len(p), out, p))
    steps, tokens = [], {}
    while eng.queue or any(r is not None for r in eng.slot_req):
        for req in eng.step():
            tokens[str(req.request_id)] = [int(t) for t in req.out_tokens]
        st, s = eng.state, eng.stats
        steps.append({
            **{k: np.asarray(st[k]).tolist()
               for k in ("buf_hits", "buf_misses", "buf_hits_l",
                         "buf_misses_l", "pf_inserted", "pf_useful")},
            "totals": [s.buffer_hits, s.buffer_misses,
                       s.prefetched_entries, s.prefetch_useful],
            "sizes": eng.buffer_sizes and [int(x) for x in eng.buffer_sizes],
        })
        assert len(steps) < 200
    return {"steps": steps, "tokens": tokens}


@pytest.mark.parametrize("case", sorted(EXACT_CASES))
def test_counters_and_tokens_match_recorded(case):
    """Every step's hits, misses (total and per layer), prefetch inserts
    and useful prefetches, the LayerSizer's sizes, and every served token
    equal the recorded run's: the hot tier keeps the same residency and
    the same LRU victims step by step."""
    want = json.loads(EXACT_DATA.read_text())[case]
    got = _exact_run(case)
    assert got["tokens"] == want["tokens"]
    assert len(got["steps"]) == len(want["steps"])
    for i, (g, w) in enumerate(zip(got["steps"], want["steps"])):
        assert g == w, i
    # the run exercises what it pins down
    last = got["steps"][-1]["totals"]
    assert last[0] > 0 and last[1] > 0 and last[2] > 0 and last[3] > 0


@pytest.mark.parametrize("prefetch", [False, True])
def test_fill_engagement_counters_add_up(prefetch):
    """The hot tier's engagement counters, read once when a request
    finishes, are the sums of that request's own steps: a miss layer-step
    for every layer whose ``buf_misses_l`` was nonzero, and the chunks
    its fills took.  Reading them costs no device read a step."""
    from repro.core import hisparse
    cfg = get_config("qwen2-1.5b").reduced()
    eng = Engine(cfg, slots=2, max_ctx=96, prefetch=prefetch, seed=0)
    width = hisparse._fill_width(cfg.sac.topk)
    prompt = np.arange(40, dtype=np.int32) % cfg.vocab
    req = Request(0, 0.0, len(prompt), 6, prompt)
    eng.submit(req)
    miss_steps = chunks = steps = 0
    reads_a_step = []
    slot = None
    while True:
        reads = eng.stats.device_reads
        done = eng.step()
        if slot is None:
            slot = eng.slot_req.index(req)
        misses = np.asarray(eng.state["buf_misses_l"])[:, slot]
        n_slots = np.asarray(
            (eng.state["hot_buf"].slot_pos[:, slot] != hisparse.DISABLED)
            .sum(-1))
        miss_steps += int((misses > 0).sum())
        chunks += int((-(-np.minimum(misses, n_slots) // width)).sum())
        steps += 1
        if done:
            break
        if steps > 1:
            reads_a_step.append(eng.stats.device_reads - reads)
    assert eng.stats.hot_tier_miss_layer_steps == miss_steps > 0
    assert eng.stats.hot_tier_fill_chunks == chunks >= miss_steps
    # the reads of a step that admits and finishes nothing: the token,
    # the cache lengths, four hot-tier counters and, with prefetch, two
    # more
    assert set(reads_a_step) == {8 if prefetch else 6}
