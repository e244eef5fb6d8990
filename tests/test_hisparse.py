"""HiSparse hierarchical buffer: unit + hypothesis property tests.

Invariants (the HiSparse swap-in contract):
  I1. page_table/slot_pos are mutually consistent bijections;
  I2. after swap_in, every (deduped, fillable) requested position is
      resident;
  I3. a hit is exactly a valid lane whose position was resident before
      the step (the buffer holds positions, never values: reads come from
      the pool, so it changes traffic, never results);
  I4. hits + misses == number of valid deduped lanes;
  I5. current-step hits are never evicted by the same step's misses;
  I6. the fills over compacted miss lanes leave the states and counts of
      the per-lane passes (tests/hisparse_oracle.py) after every step.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

import hisparse_oracle as oracle
from repro.core import hisparse


def _consistent(state):
    B, buf = state.slot_pos.shape
    S = state.page_table.shape[1]
    pt = np.asarray(state.page_table)
    sp = np.asarray(state.slot_pos)
    for b in range(B):
        for slot in range(buf):
            pos = sp[b, slot]
            if pos >= 0:
                assert pt[b, pos] == slot, (b, slot, pos)
        for pos in range(S):
            slot = pt[b, pos]
            if slot >= 0:
                assert sp[b, slot] == pos, (b, pos, slot)


def _resident_lanes(state, idx, valid):
    """Per request, the valid lanes of idx resident in ``state``."""
    _, hit = hisparse.lookup(state, idx)
    return np.asarray(hit & valid).sum(1)


def test_swap_in_basic_residency():
    B, S, buf, k = 2, 32, 8, 4
    state = hisparse.init_buffer(B, buf, S)
    idx = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    valid = jnp.ones((B, k), bool)
    state, hits, misses = hisparse.swap_in(state, idx, valid)
    assert (np.asarray(hits) == 0).all()
    assert (np.asarray(misses) == k).all()
    _consistent(state)
    slots, hit = hisparse.lookup(state, idx)
    assert bool(hit.all())
    # second time: all hits
    state, hits, misses = hisparse.swap_in(state, idx, valid)
    assert (np.asarray(hits) == k).all() and (np.asarray(misses) == 0).all()


def test_lru_eviction_order():
    B, S, buf = 1, 64, 4
    state = hisparse.init_buffer(B, buf, S)

    def touch(state, positions):
        idx = jnp.array([positions], jnp.int32)
        return hisparse.swap_in(state, idx, jnp.ones_like(idx, bool))[0]

    state = touch(state, [0, 1])     # clock 1
    state = touch(state, [2, 3])     # clock 2: buffer full {0,1,2,3}
    state = touch(state, [0, 1])     # clock 3: refresh 0,1
    state = touch(state, [10, 11])   # clock 4: must evict 2,3 (LRU)
    _, hit = hisparse.lookup(state, jnp.array([[0, 1, 10, 11]], jnp.int32))
    assert bool(hit.all())
    _, hit23 = hisparse.lookup(state, jnp.array([[2, 3]], jnp.int32))
    assert not bool(hit23.any())


def test_protected_hits_not_evicted():
    B, S, buf = 1, 64, 4
    state = hisparse.init_buffer(B, buf, S)
    idx0 = jnp.array([[0, 1, 2, 3]], jnp.int32)
    state, _, _ = hisparse.swap_in(state, idx0, jnp.ones_like(idx0, bool))
    # step: 2 hits (0,1 — LRU-oldest) + 2 misses -> must evict 2,3 not 0,1
    idx1 = jnp.array([[0, 1, 20, 21]], jnp.int32)
    state, hits, misses = hisparse.swap_in(state, idx1,
                                           jnp.ones_like(idx1, bool))
    assert int(hits[0]) == 2 and int(misses[0]) == 2
    _, hit = hisparse.lookup(state, idx1)
    assert bool(hit.all())
    _consistent(state)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_property_read_through_equals_pool(data):
    """I3/I4: the hits are exactly the valid lanes resident before the
    step (the lanes a buffer would serve; all lanes read the pool);
    accounting exact."""
    B = data.draw(st.integers(1, 3))
    S = data.draw(st.sampled_from([16, 32]))
    buf = data.draw(st.sampled_from([4, 8, 16]))
    k = data.draw(st.sampled_from([2, 4, 8]))
    steps = data.draw(st.integers(1, 5))
    state = hisparse.init_buffer(B, buf, S)
    rng = np.random.default_rng(data.draw(st.integers(0, 99)))
    for _ in range(steps):
        idx = jnp.asarray(rng.integers(0, S, (B, k)), jnp.int32)
        valid = jnp.asarray(rng.random((B, k)) < 0.9)
        resident = _resident_lanes(state, idx, valid)
        state, hits, misses = hisparse.swap_in(state, idx, valid)
        np.testing.assert_array_equal(np.asarray(hits), resident)
        v = np.asarray(valid)
        _consistent(state)
        # I4: hits+misses == valid deduped lanes
        for b in range(B):
            seen = set()
            dedup = 0
            for j in range(k):
                if v[b, j] and int(idx[b, j]) not in seen:
                    seen.add(int(idx[b, j]))
                    dedup += 1
            dup_hits = sum(1 for j in range(k)
                           if v[b, j] and list(np.asarray(idx[b])).index(
                               int(idx[b, j])) != j)
            total = int(hits[b]) + int(misses[b])
            assert total >= dedup and total <= dedup + dup_hits + k


def test_hit_rate_grounding():
    """The simulator's hit model must be in the ballpark of the real
    buffer under a drifting top-k workload (grounds serving/simulator)."""
    from repro.serving.simulator import hit_rate as model_hit
    B, S = 1, 2048
    k, buf = 64, 192  # k/buf = 1/3 like 2048/6144
    state = hisparse.init_buffer(B, buf, S)
    rng = np.random.default_rng(0)
    # drifting top-k: mostly same set, a few swaps per step
    current = rng.choice(S, size=k, replace=False)
    hits = misses = 0
    for step in range(60):
        n_swap = rng.integers(0, max(2, k // 16))
        drop = rng.choice(k, size=n_swap, replace=False)
        newpos = rng.integers(0, S, n_swap)
        current[drop] = newpos
        idx = jnp.asarray(current[None, :], jnp.int32)
        state, h, m = hisparse.swap_in(state, idx, jnp.ones((1, k), bool))
        if step >= 10:  # skip warmup
            hits += int(h[0]); misses += int(m[0])
    real = hits / (hits + misses)
    modeled = model_hit(buf, k, 32768)
    assert abs(real - modeled) < 0.12, (real, modeled)


# ---------------------------------------------------------------------------
# online re-sizing (ISSUE 4: hisparse.resize_layers)
# ---------------------------------------------------------------------------


def _layered_consistent(state):
    L, B = state.slot_pos.shape[:2]
    for layer in range(L):
        _consistent(hisparse.BufferState(*(t[layer] for t in state)))


def test_resize_layers_grow_shrink_preserves_residents():
    st = hisparse.init_layered_buffer(2, 1, [4, 2], 16, buf_max=6)
    idx = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    st, ins = hisparse.warm_lane(st, 0, idx, jnp.ones((2, 3), bool))
    assert int(ins) == 5                       # layer 1 capped at 2 slots
    st2 = hisparse.resize_layers(st, [2, 5])
    _layered_consistent(st2)
    sp = np.asarray(st2.slot_pos)[:, 0]
    pt = np.asarray(st2.page_table)[:, 0]
    # layer 0 shrank: slots 0-1 keep their positions, 2+ disabled and
    # their position unmapped
    assert sp[0].tolist() == [0, 1, -2, -2, -2, -2]
    assert pt[0][2] == -1
    # layer 1 grew: residents kept, new slots open EMPTY
    assert sp[1].tolist() == [3, 4, -1, -1, -1, -2]
    assert pt[1][3] == 0 and pt[1][4] == 1
    # surviving slots are untouched: their positions, the page table's
    # mapping of those positions, their clocks and prefetch flags
    old_sp = np.asarray(st.slot_pos)[:, 0, :2]
    np.testing.assert_array_equal(sp[:, :2], old_sp)
    for layer in range(2):
        for slot, pos in enumerate(old_sp[layer]):
            assert pt[layer][pos] == slot
    for f in ("last_use", "pf_flag"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st2, f))[:, 0, :2],
            np.asarray(getattr(st, f))[:, 0, :2])


def test_resize_layers_roundtrip_restores_capacity_not_residency():
    st = hisparse.init_layered_buffer(1, 2, [4], 8)
    idx = jnp.array([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    st, _, _ = hisparse.swap_in(
        hisparse.BufferState(*(t[0] for t in st)), idx,
        jnp.ones((2, 4), bool))
    st = hisparse.BufferState(*(t[None] for t in st))
    shrunk = hisparse.resize_layers(st, [1])
    back = hisparse.resize_layers(shrunk, [4])
    _layered_consistent(back)
    sp = np.asarray(back.slot_pos)[0]
    # capacity restored, but the evicted residents are honestly gone
    assert (sp >= -1).all()
    assert (sp >= 0).sum() == 2                # one survivor per lane


def test_resize_layers_read_through_stays_bit_identical():
    """After an arbitrary resize, demand reads still count exactly the
    lanes still resident as hits — displaced entries just miss (traffic,
    not tokens: every lane reads the pool)."""
    B, S = 2, 12
    st = hisparse.init_layered_buffer(1, B, [6], S)
    rng = np.random.default_rng(3)
    flat = hisparse.BufferState(*(t[0] for t in st))
    for step in range(8):
        idx = jnp.asarray(rng.integers(0, S, (B, 4)), jnp.int32)
        valid = jnp.ones((B, 4), bool)
        resident = _resident_lanes(flat, idx, valid)
        flat, hits, _ = hisparse.swap_in(flat, idx, valid)
        np.testing.assert_array_equal(np.asarray(hits), resident)
        _consistent(flat)
        if step == 3:
            layered = hisparse.BufferState(*(t[None] for t in flat))
            layered = hisparse.resize_layers(layered, [3])
            _layered_consistent(layered)
            flat = hisparse.BufferState(*(t[0] for t in layered))


def test_init_layered_buffer_buf_max_headroom():
    st = hisparse.init_layered_buffer(2, 1, [4, 2], 8, buf_max=7)
    assert st.slot_pos.shape[2] == 7
    sp = np.asarray(st.slot_pos)[:, 0]
    assert (sp[0] == -1).sum() == 4 and (sp[0] == -2).sum() == 3
    assert (sp[1] == -1).sum() == 2 and (sp[1] == -2).sum() == 5
    with pytest.raises(AssertionError):
        hisparse.init_layered_buffer(1, 1, [4], 8, buf_max=2)


# ---------------------------------------------------------------------------
# differential: fills over compacted miss lanes == the per-lane passes
# ---------------------------------------------------------------------------

ORACLE_FIELDS = ("slot_pos", "page_table", "last_use", "clock", "pf_flag",
                 "pf_inserted", "pf_used")


def _same_state(new, old, what):
    for f in ORACLE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(new, f)),
                                      np.asarray(getattr(old, f)),
                                      err_msg=f"{what}: {f}")


def _distinct_lanes(rng, B, S, k, ctx, keep, prev=None):
    """top_k-like lanes: k distinct positions a row, the valid ones (below
    ``ctx``) position-sorted and the invalid ones last; each row keeps a
    ``keep`` share of its previous valid set (the drift of a top-k)."""
    idx = np.zeros((B, k), np.int32)
    valid = np.zeros((B, k), bool)
    for b in range(B):
        n_valid = min(k, ctx)
        old = [] if prev is None else list(prev[b])
        kept = [p for p in old if rng.random() < keep][:n_valid]
        fresh = [p for p in rng.permutation(ctx) if p not in kept]
        chosen = sorted(kept + fresh[:n_valid - len(kept)])
        # invalid lanes hold the positions top_k would: masked ones >= ctx
        rest = list(range(ctx, S))[:k - n_valid]
        idx[b] = chosen + rest + [0] * (k - n_valid - len(rest))
        valid[b, :n_valid] = True
    return idx, valid


def _tail_lanes(rng, S, demand, w, budget):
    """Speculation lanes: w positions a row outside its demand set,
    distinct, with a pf_budget-style cap on the lanes that may issue."""
    B = demand.shape[0]
    idx = np.zeros((B, w), np.int32)
    for b in range(B):
        pool = [p for p in rng.permutation(S) if p not in set(demand[b])]
        idx[b] = pool[:w]
    valid = np.arange(w)[None, :] < np.asarray(budget)[:, None]
    return idx, valid


def _check_step(new, old, idx, valid, tail, distinct, step, counters):
    """One decode step on both: swap-in then warm insert; states and
    counts equal after each, and the engagement counters follow."""
    idx, valid = jnp.asarray(idx), jnp.asarray(valid)
    new, hits, misses = hisparse.swap_in(new, idx, valid, distinct=distinct)
    old, o_hits, o_misses, o_fills = oracle.swap_in(old, idx, valid)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(o_hits))
    np.testing.assert_array_equal(np.asarray(misses), np.asarray(o_misses))
    _same_state(new, old, f"swap_in step {step}")
    width = hisparse._fill_width(idx.shape[1])
    counters[0] += -(-np.asarray(o_fills) // width)
    counters[1] += np.asarray(o_misses) > 0
    np.testing.assert_array_equal(np.asarray(new.fill_chunks), counters[0])
    np.testing.assert_array_equal(np.asarray(new.miss_steps), counters[1])
    if tail is not None:
        t_idx, t_valid = jnp.asarray(tail[0]), jnp.asarray(tail[1])
        new, ins = hisparse.warm_insert(new, t_idx, t_valid,
                                        distinct=distinct)
        old, o_ins = oracle.warm_insert(old, t_idx, t_valid)
        np.testing.assert_array_equal(np.asarray(ins), np.asarray(o_ins))
        _same_state(new, old, f"warm_insert step {step}")
    return new, old, np.asarray(misses)


def _counters(B):
    return [np.zeros((B,), np.int64), np.zeros((B,), np.int64)]


@pytest.mark.parametrize("B,S,buf,k,w,keep,steps", [
    (2, 64, 32, 16, 8, 0.9, 8),    # the test configs' shapes, drifting
    (2, 64, 32, 16, 8, 0.0, 4),    # every lane a miss: k / width chunks
    (3, 96, 24, 16, 0, 0.5, 6),    # fills run out of slots: LRU reuse
    (2, 64, 12, 16, 4, 0.5, 5),    # k > buf: misses beyond the slots
    (2, 40, 32, 16, 8, 0.9, 6),    # short contexts: invalid lanes last
    (1, 256, 96, 48, 16, 0.8, 6),  # width 6, partly filled chunks
])
def test_differential_distinct_lanes(B, S, buf, k, w, keep, steps):
    """top_k-like (distinct, position-sorted) demand lanes and a budget-
    capped speculation tail: the compacted fills leave every field and
    count of the per-lane passes, step after step."""
    rng = np.random.default_rng(B * 1000 + buf + k)
    new = hisparse.init_buffer(B, buf, S)
    old = new
    counters = _counters(B)
    prev = None
    seen_misses = set()
    for step in range(steps):
        ctx = min(S, max(k // 2, S - 8 + step)) if S == 40 else S
        idx, valid = _distinct_lanes(rng, B, S, k, ctx, keep, prev)
        prev = [idx[b][valid[b]] for b in range(B)]
        tail = None
        if w:
            budget = rng.integers(0, w + 1, (B,))
            tail = _tail_lanes(rng, S, idx, w, budget)
        new, old, misses = _check_step(new, old, idx, valid, tail, True,
                                       step, counters)
        seen_misses.update(int(m) for m in misses)
        _consistent(new)
    assert max(seen_misses) > 0


@pytest.mark.parametrize("case", ["below", "at", "above", "above_slots"])
def test_differential_miss_counts_around_width(case):
    """A row whose misses sit below, at or above one chunk's width, or
    above the layer's slots, beside a row with none: the loop runs the
    most chunks any row needs and every row's fills land as the
    oracle's."""
    S, k = 128, 16
    width = hisparse._fill_width(k)
    buf = 12 if case == "above_slots" else 20
    n = {"below": width - 1, "at": width, "above": 3 * width + 1,
         "above_slots": k}[case]
    rng = np.random.default_rng(n)
    new = old = hisparse.init_buffer(2, buf, S)
    counters = _counters(2)
    base = np.sort(rng.choice(S, k, replace=False)).astype(np.int32)
    full = np.ones((2, k), bool)
    idx = np.stack([base, base])
    new, old, _ = _check_step(new, old, idx, full, None, True, 0, counters)
    # row 0 swaps n of its lanes for new positions, row 1 repeats itself
    fresh = [p for p in rng.permutation(S) if p not in set(base)][:n]
    row0 = np.sort(np.concatenate([base[n:], fresh])).astype(np.int32)
    idx = np.stack([row0, base])
    new, old, misses = _check_step(new, old, idx, full, None, True, 1,
                                   counters)
    assert misses[0] == n
    if case == "above_slots":
        assert n > buf
    else:
        assert misses[1] == 0


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_property_differential_repeated_lanes(data):
    """Injected lanes (a ``topk_fn`` or ``prefetch_fn``) may repeat a
    position and mark any lane invalid: with the dedupe on, the
    compacted fills still match the per-lane passes after every step."""
    B = data.draw(st.integers(1, 3))
    S = data.draw(st.sampled_from([16, 32]))
    buf = data.draw(st.sampled_from([4, 8, 16]))
    k = data.draw(st.sampled_from([2, 4, 8, 16]))
    w = data.draw(st.sampled_from([0, 1, 3]))
    rng = np.random.default_rng(data.draw(st.integers(0, 99)))
    new = old = hisparse.init_buffer(B, buf, S)
    counters = _counters(B)
    for step in range(data.draw(st.integers(1, 5))):
        idx = rng.integers(0, S, (B, k)).astype(np.int32)
        valid = rng.random((B, k)) < 0.9
        tail = None
        if w:
            tail = (rng.integers(0, S, (B, w)).astype(np.int32),
                    rng.random((B, w)) < 0.9)
        new, old, _ = _check_step(new, old, idx, valid, tail, False, step,
                                  counters)


@pytest.mark.parametrize("sizes,resize_to", [
    ([12, 5], None),          # per-layer sizes from init_layered_buffer
    ([12, 5], [4, 14]),       # then re-apportioned mid-trace
    ([0, 16], [16, 3]),       # a layer with no slots, then one shrunk
])
def test_differential_disabled_slots(sizes, resize_to):
    """DISABLED slots (the LayerSizer's per-layer capacities) are never
    assigned, before and after ``resize_layers``: each layer's compacted
    fills equal the per-lane passes, with misses beyond its slots."""
    B, S, k, w = 2, 64, 16, 6
    st_new = hisparse.init_layered_buffer(2, B, sizes, S, buf_max=16)
    st_old = st_new
    rng = np.random.default_rng(sum(sizes))
    counters = [_counters(B) for _ in sizes]
    prev = [None, None]
    for step in range(6):
        if step == 3 and resize_to is not None:
            st_new = hisparse.resize_layers(st_new, resize_to)
            st_old = hisparse.resize_layers(st_old, resize_to)
        layers_new, layers_old = [], []
        for layer in range(len(sizes)):
            flat_new = hisparse.BufferState(*(t[layer] for t in st_new))
            flat_old = hisparse.BufferState(*(t[layer] for t in st_old))
            idx, valid = _distinct_lanes(rng, B, S, k, S, 0.6, prev[layer])
            prev[layer] = [idx[b] for b in range(B)]
            tail = _tail_lanes(rng, S, idx, w, np.full((B,), w))
            disabled = np.asarray(flat_new.slot_pos) == hisparse.DISABLED
            flat_new, flat_old, _ = _check_step(
                flat_new, flat_old, idx, valid, tail, True, step,
                counters[layer])
            # the step hands no DISABLED slot a position
            np.testing.assert_array_equal(
                np.asarray(flat_new.slot_pos) == hisparse.DISABLED, disabled)
            layers_new.append(flat_new)
            layers_old.append(flat_old)
        st_new = hisparse.BufferState(
            *(jnp.stack(t) for t in zip(*layers_new)))
        st_old = hisparse.BufferState(
            *(jnp.stack(t) for t in zip(*layers_old)))
        _layered_consistent(st_new)
    # a layer's DISABLED slots stay DISABLED: never handed a position
    final = np.asarray(st_new.slot_pos)
    want = resize_to if resize_to is not None else sizes
    for layer, size in enumerate(want):
        assert (final[layer][:, size:] == hisparse.DISABLED).all()
