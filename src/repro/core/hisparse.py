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

On a TPU a scatter or gather costs about 10 ns an index whether its lane
does work or writes the sink, so the passes are arranged by how many
lanes they touch.  Lane-wide, over the step's k lanes: the page-table
lookup and one scatter of the hit mask; the dedupe of repeated
positions (a scatter-min and a gather) only where lanes can repeat — not
for ``lax.top_k``'s lanes, which are distinct (``distinct``).  Dense:
the victim order (an argsort of the slots), the miss ranks (a cumsum),
and the hit slots' clocks and prefetch flags.  Over compacted miss lanes:
the fills, in chunks of ``_fill_width(k)`` lanes gathered by a dense
compare of ranks, as many chunks as the row with the most fills needs.

The returned ``hits``/``misses`` counts drive the transfer cost model:
only misses cross the fabric (paper §5.5 — a larger buffer lowers miss
traffic, which is exactly what Fig 14 measures).
"""
from __future__ import annotations

import functools
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

    ``fill_chunks`` and ``miss_steps`` count, per request, the fill
    chunks its demand swap-ins ran and the swap-ins that had a miss: how
    far the miss compaction is engaged.
    """
    slot_pos: jnp.ndarray     # [B, buf]      global position held by slot (-1 empty)
    page_table: jnp.ndarray   # [B, S]        position -> slot (-1 not resident)
    last_use: jnp.ndarray     # [B, buf]      LRU clocks
    clock: jnp.ndarray        # [B]           step counter
    pf_flag: jnp.ndarray      # [B, buf]      slot was prefetched, not yet used
    pf_inserted: jnp.ndarray  # [B]           cumulative warm-inserted entries
    pf_used: jnp.ndarray      # [B]           cumulative prefetched-then-hit
    fill_chunks: jnp.ndarray  # [B]           cumulative demand fill chunks
    miss_steps: jnp.ndarray   # [B]           cumulative swap-ins with a miss


def init_buffer(batch: int, buf_size: int, seq_len: int) -> BufferState:
    return BufferState(
        slot_pos=jnp.full((batch, buf_size), EMPTY),
        page_table=jnp.full((batch, seq_len), EMPTY),
        last_use=jnp.zeros((batch, buf_size), jnp.int32),
        clock=jnp.zeros((batch,), jnp.int32),
        pf_flag=jnp.zeros((batch, buf_size), bool),
        pf_inserted=jnp.zeros((batch,), jnp.int32),
        pf_used=jnp.zeros((batch,), jnp.int32),
        fill_chunks=jnp.zeros((batch,), jnp.int32),
        miss_steps=jnp.zeros((batch,), jnp.int32),
    )


def lookup(state: BufferState, idx: jnp.ndarray
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Which of idx [B, k] are resident?  -> (slots [B,k], hit [B,k])."""
    slots = jnp.take_along_axis(state.page_table, idx, axis=1)
    return slots, slots >= 0


def _fill_width(lanes: int) -> int:
    """Lanes per fill chunk for a pass over ``lanes`` candidate lanes:
    about an eighth of them, in whole 128-lane tiles once an eighth
    reaches one (2048 top-k lanes -> 256, 512 speculation lanes -> 64).
    Shapes alone set it: a chunk costs its width in per-index work
    however many of its lanes are active."""
    m = -(-lanes // 8)
    return m if m < 128 else -(-m // 128) * 128


def _first_occurrence(idx, lanes, S):
    """Of one row's ``lanes`` ([k] mask), those whose position no earlier
    lane of the mask holds: a scatter-min and a gather over the k lanes,
    run only where lanes can repeat a position."""
    k = idx.shape[0]
    order = jnp.arange(k, dtype=jnp.int32)
    key = jnp.where(lanes, idx, S)
    first = jnp.full((S + 1,), k, jnp.int32).at[key].min(order)
    return lanes & (first[key] == order)


def _victim_order(slot_pos, last_use, protected):
    """One row's slots in eviction order: empty slots first (lowest index
    first), then least recently used, ``protected`` slots next to last
    and DISABLED slots strictly last; ties go to the lower slot index."""
    buf = slot_pos.shape[0]
    key = jnp.where(slot_pos == EMPTY,
                    jnp.arange(buf, dtype=jnp.int32) - _BIG,
                    jnp.where(slot_pos == DISABLED, _BIG,
                              jnp.where(protected, _BIG - 1, last_use)))
    return jnp.argsort(key).astype(jnp.int32)


def _fill_chunk(c, sp, pt, lu, pf, idx, lanes, rank, n_fill, victims,
                clock, *, width, flag):
    """Fill ranks [c * width, (c + 1) * width) of one row (vmapped over B).

    Rank r maps the lane holding the r-th filling position to slot
    ``victims[r]``.  The chunk's lanes are gathered densely (each rank
    compared with every lane's) and its victims are a slice, so only the
    read of the evicted positions and the five updates pay per index,
    ``width`` each.  sp/pt/lu/pf carry a sink row at the end,
    which every inactive lane writes; pf is int32 here, for the reason
    the hit mask is.
    """
    buf = sp.shape[0] - 1
    S = pt.shape[0] - 1
    r = c * width + jnp.arange(width, dtype=jnp.int32)
    active = r < n_fill
    pos = jnp.where(lanes[None, :] & (rank[None, :] == r[:, None]),
                    idx[None, :], 0).sum(1)                 # [width]
    slot = jnp.where(active,
                     jax.lax.dynamic_slice_in_dim(victims, c * width, width),
                     buf)
    old = sp[slot]                                          # evicted
    pt = pt.at[jnp.where(old >= 0, old, S)].set(EMPTY)
    pt = pt.at[jnp.where(active, pos, S)].set(slot)
    sp = sp.at[slot].set(jnp.where(active, pos, EMPTY))
    lu = lu.at[slot].set(clock)
    pf = pf.at[slot].set(jnp.int32(flag))
    return sp, pt, lu, pf


def _fill(slot_pos, page_table, last_use, pf_flag, idx, lanes, n_fill,
          victims, clock, flag):
    """Map the first ``n_fill`` [B] of each row's ``lanes`` [B, k], in lane
    order, into slots ``victims`` [B, buf] (ranks paired in order), stamp
    them with ``clock`` and set their prefetch flag to ``flag``.

    Runs ceil(max_b n_fill / width) chunks of ``width`` lanes: every fill
    lands whatever its count.  Ranks are global, so the chunks touch
    disjoint slots and positions.  Returns the four arrays and the chunks
    each row's fills took [B].
    """
    k = idx.shape[1]
    buf = slot_pos.shape[1]
    S = page_table.shape[1]
    width = _fill_width(k)
    rank = jnp.cumsum(lanes.astype(jnp.int32), axis=1) - 1
    # sink columns; the victim list is padded so a chunk's slice stays
    # inside it (the last chunk starts below min(k, buf) <= buf)
    sp = jnp.pad(slot_pos, ((0, 0), (0, 1)), constant_values=EMPTY)
    pt = jnp.pad(page_table, ((0, 0), (0, 1)), constant_values=EMPTY)
    lu = jnp.pad(last_use, ((0, 0), (0, 1)))
    pf = jnp.pad(pf_flag.astype(jnp.int32), ((0, 0), (0, 1)))
    victims = jnp.pad(victims, ((0, 0), (0, width)), constant_values=buf)
    chunk = jax.vmap(functools.partial(_fill_chunk, width=width, flag=flag),
                     in_axes=(None,) + (0,) * 10)

    def body(c, carry):
        return chunk(c, *carry, idx, lanes, rank, n_fill, victims, clock)

    chunks = (n_fill + width - 1) // width
    sp, pt, lu, pf = jax.lax.fori_loop(0, chunks.max(), body,
                                       (sp, pt, lu, pf))
    return sp[:, :buf], pt[:, :S], lu[:, :buf], pf[:, :buf] > 0, chunks


def _demand_one(slot_pos, page_table, last_use, clock, pf_flag, idx, valid,
                *, distinct):
    """One row's lane-wide half of a swap-in (vmapped over B): lookup,
    dedupe (only where lanes can repeat), the hit mask, the victim order,
    and the dense updates of hit slots."""
    S = page_table.shape[0]
    buf = slot_pos.shape[0]
    slots = page_table[idx]                                # [k]
    hit = (slots >= 0) & valid
    miss = ~hit & valid
    if not distinct:
        # repeated positions: only the first VALID occurrence fills
        # (invalid lanes must not shadow valid duplicates)
        miss = miss & _first_occurrence(idx, valid, S)
    # one scatter: the slots this step hits.  They are protected from
    # eviction, stamped with the clock, and consume their prefetch flag
    # (once per slot, whatever the lanes hitting it).  Every write to a
    # slot is the same 1, so repeats need no combiner; an int32 target
    # spares the index sort a scatter into bool costs on a TPU
    hit_mask = jnp.zeros((buf + 1,), jnp.int32) \
        .at[jnp.where(hit, slots, buf)].set(1)[:buf] > 0
    victims = _victim_order(slot_pos, last_use, hit_mask)
    n_slots = buf - (slot_pos == DISABLED).astype(jnp.int32).sum()
    n_miss = miss.astype(jnp.int32).sum()
    return (jnp.where(hit_mask, clock, last_use), pf_flag & ~hit_mask,
            (pf_flag & hit_mask).astype(jnp.int32).sum(), miss,
            jnp.minimum(n_miss, n_slots), victims,
            hit.astype(jnp.int32).sum(), n_miss)


def swap_in(state: BufferState, idx: jnp.ndarray, valid: jnp.ndarray,
            distinct: bool = False
            ) -> Tuple[BufferState, jnp.ndarray, jnp.ndarray]:
    """Batched swap-in of a demand read.  idx: [B,k]; valid: [B,k].

    Counts each valid lane resident before the step as a hit and each
    first occurrence of a non-resident one as a miss, then maps the misses
    in.  The values read are the caller's pool fetch either way — the hot
    tier changes *traffic*, never results.  Returns (state', hits [B],
    misses [B]).

    Lane-wide (k lanes a row): the page-table lookup and the scatter of
    the hit mask; with ``distinct`` False also the dedupe of repeated
    positions (a scatter-min and a gather).  ``distinct`` says the valid
    lanes hold distinct positions, as ``lax.top_k``'s do, and skips it.
    Dense: the victim order (one argsort of the slots), the miss ranks,
    and the hit slots' clocks and prefetch flags.  Over compacted miss
    lanes, ``_fill_width(k)`` at a time: the page-table, ``slot_pos``,
    ``last_use`` and ``pf_flag`` updates of the fills.  Misses beyond
    the layer's slots (``k > buf``, or a layer shrunk by DISABLED slots)
    stay unbuffered.
    """
    clock = state.clock + 1
    (last_use, pf_flag, pf_used, miss, n_fill, victims, hits,
     misses) = jax.vmap(functools.partial(_demand_one, distinct=distinct))(
        state.slot_pos, state.page_table, state.last_use, clock,
        state.pf_flag, idx, valid)
    slot_pos, page_table, last_use, pf_flag, chunks = _fill(
        state.slot_pos, state.page_table, last_use, pf_flag, idx, miss,
        n_fill, victims, clock, False)
    return (state._replace(slot_pos=slot_pos, page_table=page_table,
                           last_use=last_use, clock=clock, pf_flag=pf_flag,
                           pf_used=state.pf_used + pf_used,
                           fill_chunks=state.fill_chunks + chunks,
                           miss_steps=state.miss_steps + (misses > 0)),
            hits, misses)


# ---------------------------------------------------------------------------
# warm inserts (fetch pipeline: speculative prefetch + prefill warm-up)
# ---------------------------------------------------------------------------


def _warm_one(slot_pos, page_table, last_use, clock, idx, valid, *,
              distinct):
    """One row's lane-wide half of a warm insert (vmapped over B).

    Insert-without-read: positions already resident are skipped (no hit
    counted, no recency bump for THEIR slots beyond what the demand path
    did), and the current step's working set — slots with
    ``last_use >= clock`` (this step's hits, demand fills, and earlier
    warm inserts) — is never evicted.
    """
    S = page_table.shape[0]
    buf = slot_pos.shape[0]
    want = valid & (page_table[idx] < 0)
    if not distinct:
        want = _first_occurrence(idx, want, S)
    disabled = slot_pos == DISABLED
    prot = (last_use >= clock) & (slot_pos != EMPTY) & ~disabled
    avail = (buf - prot.astype(jnp.int32).sum()            # evictable slots
             - disabled.astype(jnp.int32).sum())
    return (want, jnp.minimum(want.astype(jnp.int32).sum(), avail),
            _victim_order(slot_pos, last_use, prot))


def warm_insert(state: BufferState, idx: jnp.ndarray, valid: jnp.ndarray,
                distinct: bool = False
                ) -> Tuple[BufferState, jnp.ndarray]:
    """Batched warm insert.  idx: [B, w]; valid: [B, w].

    Makes pool positions resident WITHOUT serving a read — no hit/miss is
    counted, current-step hits are never evicted, and already resident
    positions are skipped.  Inserted slots get the current clock: the
    speculation is that they are next step's hits, so they age exactly
    like this step's demand entries.  Returns (state', inserted [B]); the
    cumulative ``pf_inserted`` counter advances by the same amount.

    The passes are ``swap_in``'s: a lane-wide lookup (and, unless
    ``distinct``, the dedupe), then the fills over compacted lanes.
    """
    want, n_fill, victims = jax.vmap(
        functools.partial(_warm_one, distinct=distinct))(
            state.slot_pos, state.page_table, state.last_use, state.clock,
            idx, valid)
    slot_pos, page_table, last_use, pf_flag, _ = _fill(
        state.slot_pos, state.page_table, state.last_use, state.pf_flag,
        idx, want, n_fill, victims, state.clock, True)
    return (state._replace(slot_pos=slot_pos, page_table=page_table,
                           last_use=last_use, pf_flag=pf_flag,
                           pf_inserted=state.pf_inserted + n_fill),
            n_fill)


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
        fill_chunks=jnp.zeros((n_layers, batch), jnp.int32),
        miss_steps=jnp.zeros((n_layers, batch), jnp.int32),
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

    return state._replace(
        slot_pos=unflat(slot_pos),
        page_table=unflat(page_table), last_use=unflat(last_use),
        pf_flag=unflat(pf_flag))


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
        fill_chunks=state.fill_chunks.at[:, lane].set(0),
        miss_steps=state.miss_steps.at[:, lane].set(0),
    )
