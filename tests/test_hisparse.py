"""HiSparse hierarchical buffer: unit + hypothesis property tests.

Invariants (the HiSparse swap-in contract):
  I1. page_table/slot_pos are mutually consistent bijections;
  I2. after swap_in, every (deduped, fillable) requested position is
      resident;
  I3. a hit is exactly a valid lane whose position was resident before
      the step (the buffer holds positions, never values: reads come from
      the pool, so it changes traffic, never results);
  I4. hits + misses == number of valid deduped lanes;
  I5. current-step hits are never evicted by the same step's misses.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

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
