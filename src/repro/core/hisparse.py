"""Functional HiSparse hierarchical device buffer (paper Appendix C).

The decode instance keeps a small hot tier of KV entries in device HBM
(``device_buffer_size`` entries per request).  Every decode step the
swap-in performs, per request, the three operations of the HiSparse CUDA
kernel — all as pure JAX ops with static shapes so the whole thing is
jit/vmap-able and property-testable:

  1. **miss identification** — which of the step's top-k positions are not
     resident in the buffer (page-table lookup);
  2. **LRU eviction** — pick the least-recently-used resident slots that
     are *not* part of the current top-k as eviction victims (empty slots
     are filled first);
  3. **page-table update** — unmap victims, map fetched pages in, bump
     recency clocks.

The tier tracks residency only: which position each slot holds, never the
entry's values.  Every decode path fetches all top-k rows from the pool
before the tier is consulted, so the values a hit would serve are the
fetched ones, and a stored copy would be read by nothing.

All scatters use a padding "sink" row (index ``buf``/``S``) for inactive
lanes so no two active lanes ever write the same slot — scatter-set order
is therefore deterministic.

The returned ``hits``/``misses`` counts drive the transfer cost model:
only misses cross the fabric (paper §5.5 — a larger buffer lowers miss
traffic, which is exactly what Fig 14 measures).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

EMPTY = jnp.int32(-1)
# per-layer buffer sizing (serving/arbiter.py LayerSizer): a DISABLED
# slot belongs to no layer budget — it is never empty, never a victim,
# and never assigned, so a layered buffer can give each layer its own
# effective size inside one static [L, B, buf_max, ...] allocation
DISABLED = jnp.int32(-2)
_BIG = jnp.int32(1 << 30)


class BufferState(NamedTuple):
    """Per-request hot-tier state (all leading dims = [B, ...]).

    The ``pf_*`` fields are the speculative-prefetch bookkeeping of the
    fetch pipeline (serving/prefetch.py): ``pf_flag`` marks slots filled
    by ``warm_insert`` that have not been demand-hit yet; ``pf_inserted``
    / ``pf_used`` are cumulative per-request counters, so prefetch
    precision is measured *in-graph* (``wasted == inserted - used``).
    """
    slot_pos: jnp.ndarray     # [B, buf]      global position held by slot (-1 empty)
    page_table: jnp.ndarray   # [B, S]        position -> slot (-1 not resident)
    last_use: jnp.ndarray     # [B, buf]      LRU clocks
    clock: jnp.ndarray        # [B]           step counter
    pf_flag: jnp.ndarray      # [B, buf]      slot was prefetched, not yet used
    pf_inserted: jnp.ndarray  # [B]           cumulative warm-inserted entries
    pf_used: jnp.ndarray      # [B]           cumulative prefetched-then-hit


def init_buffer(batch: int, buf_size: int, seq_len: int) -> BufferState:
    return BufferState(
        slot_pos=jnp.full((batch, buf_size), EMPTY),
        page_table=jnp.full((batch, seq_len), EMPTY),
        last_use=jnp.zeros((batch, buf_size), jnp.int32),
        clock=jnp.zeros((batch,), jnp.int32),
        pf_flag=jnp.zeros((batch, buf_size), bool),
        pf_inserted=jnp.zeros((batch,), jnp.int32),
        pf_used=jnp.zeros((batch,), jnp.int32),
    )


def lookup(state: BufferState, idx: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Which of idx [B, k] are resident?  -> (slots [B,k], hit [B,k])."""
    slots = jnp.take_along_axis(state.page_table, idx, axis=1)
    return slots, slots >= 0


def _swap_in_one(slot_pos, page_table, last_use, clock, pf_flag, idx,
                 valid):
    """Single-request swap-in (vmapped over B).

    idx: [k] positions requested this step (always in [0, S));
    valid: [k] mask of real lanes.

    Note: if ``k > buf`` overflow misses stay unbuffered; accounting of
    hits is exact because reads happen before the swap-in.
    """
    buf = slot_pos.shape[0]
    k = idx.shape[0]
    S = page_table.shape[0]
    order = jnp.arange(k, dtype=jnp.int32)

    slots = page_table[idx]                                # [k]
    hit = (slots >= 0) & valid
    miss = (~hit) & valid
    # dedupe repeated positions within idx: only the first VALID
    # occurrence fills (invalid lanes must not shadow valid duplicates)
    idx_dedup = jnp.where(valid, idx, S)
    first_occ = jnp.full((S + 1,), k, jnp.int32).at[idx_dedup].min(order)
    miss = miss & (first_occ[idx_dedup] == order)

    # eviction order: empty slots first, then LRU, protected (current hits)
    # second-to-last, DISABLED slots (per-layer sizing) strictly last and
    # outside the assignable range.
    prot = jnp.zeros((buf,), bool).at[jnp.where(hit, slots, buf - 1)].max(hit)
    empty = slot_pos == EMPTY
    disabled = slot_pos == DISABLED
    key = jnp.where(empty, jnp.arange(buf, dtype=jnp.int32) - _BIG,
                    jnp.where(disabled, _BIG,
                              jnp.where(prot, _BIG - 1, last_use)))
    victim_order = jnp.argsort(key).astype(jnp.int32)      # [buf]
    n_slots = buf - disabled.astype(jnp.int32).sum()       # layer's size

    miss_rank = jnp.cumsum(miss.astype(jnp.int32)) - 1     # [k]
    fillable = miss & (miss_rank < n_slots)
    assign = jnp.where(fillable,
                       victim_order[jnp.clip(miss_rank, 0, buf - 1)],
                       buf)                                # buf = sink row

    # --- padded updates: row S / row buf are write sinks ---
    pt = jnp.concatenate([page_table, jnp.full((1,), EMPTY)])
    sp = jnp.concatenate([slot_pos, jnp.full((1,), EMPTY)])
    old_pos = sp[assign]                                   # evicted position
    pt = pt.at[jnp.where(old_pos >= 0, old_pos, S)].set(EMPTY)
    pt = pt.at[jnp.where(fillable, idx, S)].set(assign)
    page_table = pt[:S]

    sp = sp.at[assign].set(jnp.where(fillable, idx, EMPTY))
    slot_pos = sp[:buf]

    touched = jnp.where(hit, slots, assign)                # in [0, buf]
    lu = jnp.concatenate([last_use, jnp.zeros((1,), jnp.int32)])
    last_use = lu.at[touched].set(clock)[:buf]

    # prefetch accounting: a demand hit on a prefetched slot consumes its
    # flag (counted once per slot — the scatter-max dedupes repeated idx);
    # demand fills overwrite any stale flag on the victim slot.
    hit_mask = jnp.zeros((buf + 1,), bool) \
        .at[jnp.where(hit, slots, buf)].max(hit)[:buf]
    pf_used = (pf_flag & hit_mask).astype(jnp.int32).sum()
    pf = jnp.concatenate([pf_flag & ~hit_mask, jnp.zeros((1,), bool)])
    pf_flag = pf.at[assign].set(False)[:buf]

    return (slot_pos, page_table, last_use, pf_flag, pf_used,
            hit.astype(jnp.int32).sum(), miss.astype(jnp.int32).sum())


def swap_in(state: BufferState, idx: jnp.ndarray, valid: jnp.ndarray
            ) -> Tuple[BufferState, jnp.ndarray, jnp.ndarray]:
    """Batched swap-in of a demand read.  idx: [B,k]; valid: [B,k].

    Counts each valid lane resident before the step as a hit and each
    first occurrence of a non-resident one as a miss, then maps the misses
    in.  The values read are the caller's pool fetch either way — the hot
    tier changes *traffic*, never results.  Returns (state', hits [B],
    misses [B]).
    """
    clock = state.clock + 1
    (slot_pos, page_table, last_use, pf_flag, pf_used, hits,
     misses) = jax.vmap(_swap_in_one)(
        state.slot_pos, state.page_table, state.last_use, clock,
        state.pf_flag, idx, valid)
    return (BufferState(slot_pos, page_table, last_use, clock, pf_flag,
                        state.pf_inserted, state.pf_used + pf_used),
            hits, misses)


# ---------------------------------------------------------------------------
# warm inserts (fetch pipeline: speculative prefetch + prefill warm-up)
# ---------------------------------------------------------------------------


def _warm_insert_one(slot_pos, page_table, last_use, clock, pf_flag, idx,
                     valid):
    """Single-request warm insert (vmapped over B).

    Insert-without-read: positions already resident are skipped (no hit
    counted, no recency bump for THEIR slots beyond what the demand path
    did), and the current step's working set — slots with
    ``last_use >= clock`` (this step's hits, demand fills, and earlier
    warm inserts) — is never evicted.  Inserted slots get the current
    clock: the speculation is that they are next step's hits, so they age
    exactly like this step's demand entries.
    """
    buf = slot_pos.shape[0]
    w = idx.shape[0]
    S = page_table.shape[0]
    order = jnp.arange(w, dtype=jnp.int32)

    resident = page_table[idx] >= 0
    want = valid & ~resident
    idx_dedup = jnp.where(want, idx, S)
    first_occ = jnp.full((S + 1,), w, jnp.int32).at[idx_dedup].min(order)
    want = want & (first_occ[idx_dedup] == order)

    empty = slot_pos == EMPTY
    disabled = slot_pos == DISABLED
    prot = (last_use >= clock) & ~empty & ~disabled
    key = jnp.where(empty, jnp.arange(buf, dtype=jnp.int32) - _BIG,
                    jnp.where(disabled, _BIG,
                              jnp.where(prot, _BIG - 1, last_use)))
    victim_order = jnp.argsort(key).astype(jnp.int32)      # [buf]
    avail = (buf - prot.astype(jnp.int32).sum()            # evictable slots
             - disabled.astype(jnp.int32).sum())

    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    fill = want & (rank < avail)
    assign = jnp.where(fill, victim_order[jnp.clip(rank, 0, buf - 1)],
                       buf)                                # buf = sink row

    pt = jnp.concatenate([page_table, jnp.full((1,), EMPTY)])
    sp = jnp.concatenate([slot_pos, jnp.full((1,), EMPTY)])
    old_pos = sp[assign]
    pt = pt.at[jnp.where(old_pos >= 0, old_pos, S)].set(EMPTY)
    pt = pt.at[jnp.where(fill, idx, S)].set(assign)
    page_table = pt[:S]

    sp = sp.at[assign].set(jnp.where(fill, idx, EMPTY))
    slot_pos = sp[:buf]

    lu = jnp.concatenate([last_use, jnp.zeros((1,), jnp.int32)])
    last_use = lu.at[assign].set(clock)[:buf]

    pf = jnp.concatenate([pf_flag, jnp.zeros((1,), bool)])
    pf_flag = pf.at[assign].set(fill)[:buf]

    return (slot_pos, page_table, last_use, pf_flag,
            fill.astype(jnp.int32).sum())


def warm_insert(state: BufferState, idx: jnp.ndarray, valid: jnp.ndarray
                ) -> Tuple[BufferState, jnp.ndarray]:
    """Batched warm insert.  idx: [B, w]; valid: [B, w].

    Makes pool positions resident WITHOUT serving a read — no hit/miss is
    counted, current-step hits are never evicted, and already resident
    positions are skipped.  Returns (state', inserted [B]); the
    cumulative ``pf_inserted`` counter advances by the same amount.
    """
    (slot_pos, page_table, last_use, pf_flag, ins) = jax.vmap(
        _warm_insert_one)(state.slot_pos, state.page_table, state.last_use,
                          state.clock, state.pf_flag, idx, valid)
    return (BufferState(slot_pos, page_table, last_use, state.clock,
                        pf_flag, state.pf_inserted + ins, state.pf_used),
            ins)


def warm_lane(state: BufferState, lane, idx: jnp.ndarray,
              valid: jnp.ndarray) -> Tuple[BufferState, jnp.ndarray]:
    """Warm-insert into one request lane of a layered buffer.

    state: layered ([L, B, ...]); idx: [L, w]; valid: [L, w].  The
    per-layer slices of lane ``lane`` form exactly the batched layout (L
    plays the batch axis), so this is ``warm_insert`` over layers.  Returns (state', total entries inserted) — the prefill
    warm-up path of serving/prefetch.py (radix-reused pages + top-scoring
    prompt entries seeding the hot tier).
    """
    sub = BufferState(*(t[:, lane] for t in state))
    sub, ins = warm_insert(sub, idx, valid)
    new = BufferState(*(full.at[:, lane].set(part)
                        for full, part in zip(state, sub)))
    return new, ins.sum()


# ---------------------------------------------------------------------------
# layered layout (serving engine: one buffer per pool layer)
# ---------------------------------------------------------------------------


def init_layered_buffer(n_layers: int, batch: int,
                        buf_size: Union[int, Sequence[int]],
                        seq_len: int,
                        buf_max: Union[int, None] = None) -> BufferState:
    """Per-(layer, request) buffer stack: every field gains a leading
    [L] axis (slot_pos [L, B, buf], page_table [L, B, S], ...).

    ``buf_size`` may be a single size (uniform layers, the PR 1 layout)
    or a per-layer sequence (serving/arbiter.py ``LayerSizer``): the
    allocation is ``max(sizes)`` wide and layer ``l``'s slots beyond
    ``sizes[l]`` are marked :data:`DISABLED` — never resident, never a
    victim — so each layer runs at its own effective capacity inside one
    static layout.  ``buf_max`` overrides the allocation width (must be
    >= every size): the headroom online re-sizing (``resize_layers``)
    needs to grow a layer past its initial share later.

    This is the ``hot_buf`` entry of the engine's serve_state pytree;
    the decode step threads per-layer slices through ``swap_in``.
    """
    if isinstance(buf_size, (int, np.integer)):
        sizes = [int(buf_size)] * n_layers
    else:
        sizes = [int(s) for s in buf_size]
        assert len(sizes) == n_layers, (len(sizes), n_layers)
    if buf_max is None:
        buf_max = max(max(sizes), 1)
    else:
        buf_max = int(buf_max)
        assert buf_max >= max(max(sizes), 1), (buf_max, sizes)
    slot = np.arange(buf_max)[None, None, :]
    sz = np.asarray(sizes, np.int32)[:, None, None]
    slot_pos = jnp.asarray(
        np.where(np.broadcast_to(slot < sz, (n_layers, batch, buf_max)),
                 int(EMPTY), int(DISABLED)), jnp.int32)
    return BufferState(
        slot_pos=slot_pos,
        page_table=jnp.full((n_layers, batch, seq_len), EMPTY),
        last_use=jnp.zeros((n_layers, batch, buf_max), jnp.int32),
        clock=jnp.zeros((n_layers, batch), jnp.int32),
        pf_flag=jnp.zeros((n_layers, batch, buf_max), bool),
        pf_inserted=jnp.zeros((n_layers, batch), jnp.int32),
        pf_used=jnp.zeros((n_layers, batch), jnp.int32),
    )


def _resize_one(slot_pos, page_table, last_use, pf_flag, enabled):
    """Single-lane layer re-sizing (vmapped over L*B).

    ``enabled``: [buf] bool — the slot belongs to the layer's NEW budget.
    Slots leaving the budget are evicted (their position unmapped from the
    page table) and marked DISABLED; slots entering it open as EMPTY.
    Slots enabled in both layouts are untouched — their positions, their
    recency clocks, and their prefetch flags survive the resize, so
    decoded tokens cannot change (the pool stays authoritative either
    way; only *residency* moved).
    """
    S = page_table.shape[0]
    displaced = (~enabled) & (slot_pos >= 0)
    pt = jnp.concatenate([page_table, jnp.full((1,), EMPTY)])
    pt = pt.at[jnp.where(displaced, slot_pos, S)].set(EMPTY)
    page_table = pt[:S]
    slot_pos = jnp.where(~enabled, DISABLED,
                         jnp.where(slot_pos == DISABLED, EMPTY, slot_pos))
    last_use = jnp.where(enabled, last_use, 0)
    pf_flag = pf_flag & enabled
    return slot_pos, page_table, last_use, pf_flag


def resize_layers(state: BufferState, sizes: Sequence[int]) -> BufferState:
    """Re-apportion a layered buffer's per-layer capacities IN PLACE.

    state: layered ([L, B, buf_max, ...]); sizes: [L] new per-layer slot
    budgets (each <= buf_max — the static allocation width is the hard
    ceiling).  Layer ``l`` keeps its first ``sizes[l]`` slots enabled and
    the rest DISABLED: entries displaced by a shrink are evicted (their
    next demand read is an honest miss), surviving slots keep their
    positions, and the cumulative ``pf_*`` counters are preserved
    (a displaced prefetched entry simply counts as wasted speculation,
    exactly like an LRU eviction would).

    This is the engine's online LayerSizer path (serving/arbiter.py):
    every ``resize_interval`` steps the measured per-layer miss rates
    re-apportion the hot tier without reallocating the serve state.
    """
    L, B, buf_max = state.slot_pos.shape
    sz = np.asarray([int(s) for s in sizes], np.int32)
    assert sz.shape == (L,), (sz.shape, L)
    assert sz.max(initial=0) <= buf_max and sz.min(initial=1) >= 0, \
        (sizes, buf_max)
    enabled = jnp.asarray(
        np.broadcast_to(np.arange(buf_max)[None, :] < sz[:, None],
                        (L, buf_max)))

    def flat(t):
        return t.reshape(L * B, *t.shape[2:])

    en = jnp.repeat(enabled, B, axis=0)                    # [L*B, buf]
    slot_pos, page_table, last_use, pf_flag = jax.vmap(_resize_one)(
        flat(state.slot_pos), flat(state.page_table), flat(state.last_use),
        flat(state.pf_flag), en)

    def unflat(t):
        return t.reshape(L, B, *t.shape[1:])

    return BufferState(
        slot_pos=unflat(slot_pos),
        page_table=unflat(page_table), last_use=unflat(last_use),
        clock=state.clock, pf_flag=unflat(pf_flag),
        pf_inserted=state.pf_inserted, pf_used=state.pf_used)


def reset_lane(state: BufferState, lane: int) -> BufferState:
    """Clear one request lane of a layered buffer ([L, B, ...] layout).

    Used when a serving slot is recycled: the next request must not see
    the previous occupant's residency (its pool pages are reused).  DISABLED
    slots (per-layer sizing) keep their marker: layer capacities are a
    property of the buffer layout, not of the occupant.
    """
    lane_slots = state.slot_pos[:, lane]
    cleared = jnp.where(lane_slots == DISABLED, DISABLED, EMPTY)
    return BufferState(
        slot_pos=state.slot_pos.at[:, lane].set(cleared),
        page_table=state.page_table.at[:, lane].set(EMPTY),
        last_use=state.last_use.at[:, lane].set(0),
        clock=state.clock.at[:, lane].set(0),
        pf_flag=state.pf_flag.at[:, lane].set(False),
        pf_inserted=state.pf_inserted.at[:, lane].set(0),
        pf_used=state.pf_used.at[:, lane].set(0),
    )
