"""The GPU-side **Sparse Memory Pool** with LRU eviction/admission (paper §3.2).

Fully functional, fixed-shape JAX so the whole decode step stays inside one
jit program.  Per (layer, sequence) the pool holds ``P`` latent rows; an
inverse map ``slot_of`` makes lookup O(K) gathers instead of O(K·P)
compares.

State (leading batch dim B everywhere):

* ``data     [B, P, D]``  resident latent rows
* ``ids      [B, P]``     token position occupying each slot (-1 empty)
* ``last_use [B, P]``     LRU step stamp (-1 empty)
* ``slot_of  [B, S]``     inverse map: position -> slot (-1 not resident)
* ``step     []``         monotone step counter

Fixed-shape miss handling: each step fetches at most ``M`` rows (the
provisioned H2D envelope).  ``models.mla.topk_ids`` returns ids in descending
indexer score order (``lax.top_k``'s), so when misses overflow M the
*lowest-scoring* entries are the ones dropped (masked out of attention,
softmax renormalizes exactly over the attended set).  ``stats.overflow`` counts them; sizing M per the paper's
miss profiles (16–605/batch at ratio 0.2) makes overflow rare.

Jit contract: every state transition here (``lookup`` / ``admit`` /
``tick`` / ``invalidate_beyond``) is fixed-shape and host-sync-free, so
the whole per-round sequence — including the speculative rollback —
traces into the serve loop's donated StepProgram
(:mod:`repro.serving.step`); only ``check_consistent`` is host-side
(tests/debugging).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class PoolState(NamedTuple):
    data: jax.Array        # [B, P, D]
    ids: jax.Array         # [B, P] int32
    last_use: jax.Array    # [B, P] int32
    slot_of: jax.Array     # [B, S] int32
    step: jax.Array        # [] int32


class Lookup(NamedTuple):
    slot: jax.Array        # [B, K] pool slot of each requested id (-1 miss)
    hit: jax.Array         # [B, K] bool
    miss_ids: jax.Array    # [B, M] requested-but-absent ids (-1 padding)
    miss_rank: jax.Array   # [B, K] rank of each miss among misses (or big)
    n_miss: jax.Array      # [B] int32 true miss count (incl. overflow)


class PoolStats(NamedTuple):
    hits: jax.Array        # [B]
    misses: jax.Array      # [B]
    overflow: jax.Array    # [B] misses beyond the M envelope (dropped)


def init_pool(batch: int, pool_entries: int, max_seq: int, dim: int,
              dtype=jnp.bfloat16) -> PoolState:
    return PoolState(
        data=jnp.zeros((batch, pool_entries, dim), dtype),
        ids=jnp.full((batch, pool_entries), -1, jnp.int32),
        last_use=jnp.full((batch, pool_entries), -1, jnp.int32),
        slot_of=jnp.full((batch, max_seq), -1, jnp.int32),
        step=jnp.zeros((), jnp.int32),
    )


def lookup(pool: PoolState, req_ids: jax.Array, req_valid: jax.Array,
           max_misses: int, *, slot_mask: jax.Array | None,
           dedup: bool = True) -> tuple[PoolState, Lookup, PoolStats]:
    """Resolve requested cache ids against the pool.

    req_ids [B,K] (score-descending), req_valid [B,K].  Touches hit slots
    (LRU stamp).  Returns miss buffer of fixed width ``max_misses``.

    ``slot_mask`` is **required, keyword-only** (ESS001 — see ANALYSIS.md):
    a ``[B]`` bool mask ANDs into ``req_valid`` so a frozen/freed batch row
    neither touches LRU stamps nor requests fetches; ``None`` states
    explicitly that every row is live (or that ``req_valid`` already
    encodes the gating).

    With ``dedup`` the request list may contain **duplicates** (a Q>1
    speculative-verify step flattens every draft's top-k into one list,
    and drafts routinely select the same positions).  Duplicate misses
    share the first occurrence's miss-buffer rank, so the buffer holds
    *unique* positions: each row is fetched once, and :func:`admit` never
    installs the same position into two pool slots — a duplicate admit
    left a zombie entry (forward map without inverse link) that wasted
    capacity and, on its eventual eviction, clobbered the live
    duplicate's ``slot_of`` link.  Dedup costs an O(K^2) compare; callers
    whose requests are distinct by construction (one query's top-k, a
    warmup window) pass ``dedup=False`` for the linear-rank path — the
    two are bit-identical on duplicate-free input.
    """
    B, K = req_ids.shape
    if slot_mask is not None:
        req_valid = req_valid & slot_mask[:, None]
    bi = jnp.arange(B)[:, None]
    safe_ids = jnp.clip(req_ids, 0, pool.slot_of.shape[1] - 1)
    slot = jnp.take_along_axis(pool.slot_of, safe_ids, axis=1)   # [B,K]
    hit = (slot >= 0) & req_valid
    miss = (~hit) & req_valid

    # touch hits
    touch_slot = jnp.where(hit, slot, pool.ids.shape[1])         # OOB -> drop
    last_use = pool.last_use.at[bi, touch_slot].max(
        pool.step, mode="drop")

    # pack misses (score order preserved): unique misses get consecutive
    # ranks; a duplicate miss inherits its first occurrence's rank
    if dedup:
        eq = req_ids[:, :, None] == req_ids[:, None, :]          # [B,K,K]
        earlier = jnp.tril(jnp.ones((K, K), bool), k=-1)[None]   # i < j
        dup = miss & (eq & earlier & miss[:, None, :]).any(-1)
        unique_miss = miss & ~dup
        rank_u = jnp.cumsum(unique_miss.astype(jnp.int32), axis=1) - 1
        # rank of request j = rank of the unique miss sharing its id
        # (itself when unique); at most one unique miss per id, so the
        # sum selects it
        rank = jnp.einsum("bji,bi->bj", (eq & unique_miss[:, None, :])
                          .astype(jnp.int32),
                          jnp.where(unique_miss, rank_u, 0))
    else:
        unique_miss = miss
        rank = jnp.cumsum(miss.astype(jnp.int32), axis=1) - 1
    rank = jnp.where(miss, rank, K + max_misses)                 # invalid big
    scat = jnp.where(rank < max_misses, rank, max_misses)        # OOB -> drop
    miss_ids = jnp.full((B, max_misses + 1), -1, jnp.int32)
    miss_ids = miss_ids.at[bi, scat].set(req_ids, mode="drop")[:, :max_misses]

    n_miss = unique_miss.sum(axis=1)                 # rows actually fetched
    stats = PoolStats(hits=hit.sum(axis=1), misses=n_miss,
                      overflow=jnp.maximum(n_miss - max_misses, 0))
    return (pool._replace(last_use=last_use),
            Lookup(slot, hit, miss_ids, rank, n_miss), stats)


def admit(pool: PoolState, miss_ids: jax.Array, rows: jax.Array, *,
          slot_mask: jax.Array | None,
          protect_slots: jax.Array | None = None) -> PoolState:
    """LRU-evict |M| coldest slots and write the fetched rows into them.

    miss_ids [B,M] (-1 padding rows are ignored), rows [B,M,D].
    ``slot_mask`` is **required, keyword-only** (ESS001): a ``[B]`` bool
    mask voids the admissions of masked batch rows (their pool state is
    frozen in-step); ``None`` states explicitly that every row is live or
    that masked rows' ``miss_ids`` are already all ``-1``.
    protect_slots [B,Kp]: slots that must not be evicted this step (current
    hits are protected automatically by their fresh LRU stamp as long as
    P >= K; pass explicit slots for extra safety with tiny pools).

    A Q>1 step's miss envelope can exceed the pool size (``M = ratio*K*Q``
    vs ``P`` entries); admission is then capped at the ``P``
    highest-scoring misses — the fetch itself still serves attention at
    full width, only residency is capacity-clipped.
    """
    B, M = miss_ids.shape
    if slot_mask is not None:
        miss_ids = jnp.where(slot_mask[:, None], miss_ids, -1)
    P = pool.ids.shape[1]
    if M > P:
        miss_ids, rows = miss_ids[:, :P], rows[:, :P]
        M = P
    bi = jnp.arange(B)[:, None]
    valid = miss_ids >= 0

    score = pool.last_use                                        # [B,P]
    if protect_slots is not None:
        ps = jnp.where(protect_slots >= 0, protect_slots, P)
        score = score.at[bi, ps].set(jnp.iinfo(jnp.int32).max, mode="drop")
    # coldest M slots (empty slots have last_use=-1 -> chosen first)
    _, evict = jax.lax.top_k(-score, M)                          # [B,M]

    tgt = jnp.where(valid, evict, P)                             # OOB -> drop
    old_ids = jnp.take_along_axis(pool.ids, evict, axis=1)       # [B,M]
    old_valid = (old_ids >= 0) & valid
    # clear inverse map of evicted ids
    clear_pos = jnp.where(old_valid, old_ids, pool.slot_of.shape[1])
    slot_of = pool.slot_of.at[bi, clear_pos].set(-1, mode="drop")
    # install new entries
    slot_of = slot_of.at[bi, jnp.where(valid, miss_ids,
                                       pool.slot_of.shape[1])].set(
        evict, mode="drop")
    ids = pool.ids.at[bi, tgt].set(miss_ids, mode="drop")
    last_use = pool.last_use.at[bi, tgt].set(pool.step, mode="drop")
    data = pool.data.at[bi, tgt].set(rows.astype(pool.data.dtype),
                                     mode="drop")
    return PoolState(data, ids, last_use, slot_of, pool.step)


def tick(pool: PoolState) -> PoolState:
    return pool._replace(step=pool.step + 1)


def invalidate_beyond(pool: PoolState, lens: jax.Array) -> PoolState:
    """Drop pool entries for positions >= lens[b] (speculative-decode
    rollback: rejected draft positions will be re-written with different
    content, so stale pool rows must not survive).

    Ordering contract (speculative rollback): call this **after** the
    verify step's :func:`admit` + :func:`tick`.  A Q>1 verify step's
    flattened lookup may legitimately admit rows *at draft positions*
    (query ``q`` requests positions appended by queries ``< q``); those
    entries must exist when they are invalidated, otherwise a stale
    ``slot_of`` link would survive the rollback and a later occupant of
    the position would take a hit on the rejected draft's latent.  The
    clear is total for the forward map *and* the inverse map — ``ids`` /
    ``last_use`` keyed by resident position, ``slot_of`` keyed by
    position — so it is idempotent and safe to apply to an already-clean
    slot (a frozen ``slot_mask`` row passes its unchanged ``lens``).
    """
    stale = pool.ids >= lens[:, None]                            # [B,P]
    ids = jnp.where(stale, -1, pool.ids)
    last_use = jnp.where(stale, -1, pool.last_use)
    pos = jnp.arange(pool.slot_of.shape[1])[None, :]
    slot_of = jnp.where(pos >= lens[:, None], -1, pool.slot_of)
    return pool._replace(ids=ids, last_use=last_use, slot_of=slot_of)


def check_consistent(pool: PoolState) -> bool:
    """Host-side invariant check (tests / debugging): the forward map
    (``ids``) and inverse map (``slot_of``) must mirror each other exactly
    — every resident id points back at its slot and vice versa, with no
    dangling links after admit/evict/invalidate interleavings."""
    import numpy as np
    ids = np.asarray(pool.ids)
    slot_of = np.asarray(pool.slot_of)
    last_use = np.asarray(pool.last_use)
    B, P = ids.shape
    for b in range(B):
        res = ids[b][ids[b] >= 0]
        if len(res) != len(set(res.tolist())):  # esslint: disable=ESS002 — numpy, host-only helper
            return False                     # duplicate resident position
        for s in range(P):
            if ids[b, s] >= 0 and slot_of[b, ids[b, s]] != s:
                return False                 # forward without inverse
            if ids[b, s] < 0 and last_use[b, s] >= 0:
                return False                 # empty slot with live stamp
        for pos_ in range(slot_of.shape[1]):
            if slot_of[b, pos_] >= 0 and ids[b, slot_of[b, pos_]] != pos_:
                return False                 # inverse without forward
    return True


def gather_resident(pool: PoolState, slot: jax.Array, hit: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Gather hit rows [B,K,D] from the pool (miss rows zero, masked)."""
    safe = jnp.where(hit, slot, 0)
    rows = jnp.take_along_axis(pool.data, safe[..., None], axis=1)
    return jnp.where(hit[..., None], rows, 0), hit


def pool_entries_for(ratio: float, context_len: int, topk: int,
                     min_entries: int) -> int:
    """Paper's Sparse-Memory-Ratio -> pool size; floor at max(topk, 6.4K-ish
    recommendation scaled)."""
    p = int(ratio * context_len)
    return max(p, topk, min(min_entries, context_len))
