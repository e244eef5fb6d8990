"""The hot tier's swap-in and warm insert with every pass over all k
lanes, one lane at a time: the oracle of ``tests/test_hisparse.py``'s
differential tests, which require ``repro.core.hisparse``'s fills over
compacted miss lanes to leave the same states and counts after every
step.
"""
import jax
import jax.numpy as jnp

from repro.core.hisparse import DISABLED, EMPTY, _BIG


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
            hit.astype(jnp.int32).sum(), miss.astype(jnp.int32).sum(),
            fillable.astype(jnp.int32).sum())


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


def swap_in(state, idx, valid):
    """Batched oracle swap-in: (state', hits [B], misses [B], fills [B])."""
    clock = state.clock + 1
    (slot_pos, page_table, last_use, pf_flag, pf_used, hits, misses,
     fills) = jax.vmap(_swap_in_one)(
        state.slot_pos, state.page_table, state.last_use, clock,
        state.pf_flag, idx, valid)
    return (state._replace(slot_pos=slot_pos, page_table=page_table,
                           last_use=last_use, clock=clock, pf_flag=pf_flag,
                           pf_used=state.pf_used + pf_used),
            hits, misses, fills)


def warm_insert(state, idx, valid):
    """Batched oracle warm insert: (state', inserted [B])."""
    (slot_pos, page_table, last_use, pf_flag, ins) = jax.vmap(
        _warm_insert_one)(state.slot_pos, state.page_table, state.last_use,
                          state.clock, state.pf_flag, idx, valid)
    return (state._replace(slot_pos=slot_pos, page_table=page_table,
                           last_use=last_use, pf_flag=pf_flag,
                           pf_inserted=state.pf_inserted + ins),
            ins)
