"""ESS decode attention with DA / DBA overlap (paper §3.3).

On TPU, overlap is decided by XLA's latency-hiding scheduler, so the control
knob is **program structure**: what is *independent* of the H2D fetch can
hide it.  The three strategies lower to three different dependence graphs:

* ``none``  (SGLang default): one attention over the union of hits+misses —
  everything depends on the fetch; fully serial.
* ``da``    (Dual-Attention): fetch is issued first; **Attn0** consumes only
  pool-resident rows (independent of the fetch) and **Attn1** consumes the
  fetched rows; the two partials merge exactly (online-softmax
  renormalization, bit-identical up to fp reassociation).
* ``dba``   (DualBatch-Attention): additionally splits the *indexer* along
  the batch dim; half-2's indexer compute (paged_mqa_logits + top-k — the
  components whose intensity survives batch splitting, §3.3) is independent
  of half-1's fetch and hides it even at long context where Attn0 is tiny.

All shapes fixed; Q>1 (MTP drafts) supported by flattening per-query top-k
requests into the pool lookup.  ``lens`` may be per-query ``[B,Q]`` so a
draft-verification step stays causal *within* the Q window: query ``q``
only selects (and attends to) positions ``< lens[b,q]`` — without the
per-query mask every draft could attend to entries appended by later
drafts, breaking parity with sequential single-token steps.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import lru_pool as LP
from repro.core import offload
from repro.models import mla as M

NEG_INF = -2.0e38


class ESSLayerState(NamedTuple):
    pool: LP.PoolState         # device-resident sparse memory pool
    host_latent: jax.Array     # dense [B,S,D] / [L,B,S,D] or paged page
                               # pool [NP,R,D] / [L,NP,R,D] (pinned_host)
    layer: int = 0             # layer index when host_latent is stacked [L,...]
    # DBA half-batch offset into the host cache.  May be a traced i32
    # scalar: the compiled serve round's prefill program indexes the
    # admitting slot dynamically (offload routes it through
    # dynamic_slice), so no Python-int shape leaks force a retrace.
    batch_offset: int | jax.Array = 0
    block_table: jax.Array | None = None   # [B_total, NB] paged indirection
    # per-row scale plane of a quantized host tier ([L,NP,R,1] paged /
    # [L,B,S,1] dense); None = raw bf16 tier.  Fetches below go through
    # offload.gather_tier_rows, which dequantizes at miss width — bf16
    # rows never materialize at tier width.
    host_scales: jax.Array | None = None


class ESSStats(NamedTuple):
    hits: jax.Array
    misses: jax.Array
    overflow: jax.Array


def _attend_rows(q_comb: jax.Array, rows: jax.Array, valid: jax.Array,
                 cfg: ArchConfig, use_kernel: bool = False) -> M.Partial:
    """q [B,Q,H,D] vs per-query rows [B,Q,K,D] (or shared [B,K,D])."""
    if use_kernel:
        from repro.kernels.sparse_mla import ops as sk
        return sk.partial_attend(q_comb, rows, valid, M.mla_scale(cfg),
                                 cfg.mla.kv_lora_rank)
    rank = cfg.mla.kv_lora_rank
    if rows.ndim == 3:
        rows = rows[:, None]
        valid = valid[:, None]
    s = jnp.einsum("bqhd,bqkd->bqhk", q_comb, rows,
                   preferred_element_type=jnp.float32) * M.mla_scale(cfg)
    s = jnp.where(valid[:, :, None, :], s, NEG_INF)
    mx = s.max(axis=-1)
    p = jnp.exp(s - mx[..., None])
    p = jnp.where(valid[:, :, None, :], p, 0.0)
    o = jnp.einsum("bqhk,bqkv->bqhv", p.astype(rows.dtype),
                   rows[..., :rank], preferred_element_type=jnp.float32)
    l = p.sum(axis=-1)
    return M.Partial(o, mx, l)


def ess_sparse_attention(mla_p: dict, idx_p: dict, cfg: ArchConfig,
                         x_norm: jax.Array, positions: jax.Array,
                         state: ESSLayerState, idx_keys: jax.Array,
                         lens: jax.Array, *, overlap: str = "da",
                         use_kernel: bool = False,
                         slot_mask: jax.Array | None = None
                         ) -> tuple[jax.Array, ESSLayerState, ESSStats]:
    """One layer of ESS decode attention.

    x_norm [B,Q,d] (post-ln1 hidden of the new tokens), positions [B,Q],
    idx_keys [B,S,Di] device-resident Indexer-Cache *already containing the
    new tokens' keys*, lens [B] = cache length *after* appending new tokens
    — or per-query ``[B,Q]`` (causal within the Q window: query ``q`` sees
    positions ``< lens[b,q]``; a slot-masked row passes 0).
    ``slot_mask`` [B] gates the pool mutations (LRU touches / admissions)
    of frozen batch rows in-step; it is forwarded into
    :func:`repro.core.lru_pool.lookup` / :func:`~repro.core.lru_pool.admit`.
    ``state.host_latent`` must already contain the new latent rows (the
    engine performs the D2H writeback — Figure 3's small D2H — before
    calling attention so drafts can attend to themselves).
    """
    if overlap == "dba":
        return _dba(mla_p, idx_p, cfg, x_norm, positions, state, idx_keys,
                    lens, use_kernel, slot_mask)
    return _da_or_none(mla_p, idx_p, cfg, x_norm, positions, state, idx_keys,
                       lens, overlap, use_kernel, slot_mask)


def _fetch_valid(lk, B: int, Q: int, K: int, M_env: int) -> jax.Array:
    """[B,Q,M_env] bool — which fetched rows each query actually requested.

    At Q=1 this is exactly ``miss_ids >= 0``; at Q>1 it keeps the verify
    step per-query causal: without it every draft attends the *union* of
    all drafts' missed rows (and rows it already hit double-count)."""
    bi = jnp.arange(B)[:, None]
    qidx = jnp.broadcast_to((jnp.arange(Q * K) // K)[None], (B, Q * K))
    scat = jnp.minimum(lk.miss_rank, M_env)          # non-miss rank is big
    return jnp.zeros((B, Q, M_env + 1), bool).at[
        bi, qidx, scat].set(True, mode="drop")[:, :, :M_env]


def _topk_and_lookup(idx_p, cfg, x_norm, state, idx_keys, lens, slot_mask):
    B, Q, _ = x_norm.shape
    S = idx_keys.shape[1]
    K = min(cfg.dsa.index_topk, S)
    M_env = max(1, int(cfg.ess.max_miss_ratio * K)) * Q

    with jax.named_scope("ess.indexer"):
        iq = M.indexer_query(idx_p, x_norm)
        sc = M.indexer_scores(iq, idx_keys)                      # [B,Q,S]
    with jax.named_scope("ess.topk"):
        qlens = (lens[:, None] if lens.ndim == 1 else lens)[..., None]
        ids = M.topk_ids(sc, K, jnp.arange(S) < qlens)          # [B,Q,K]
        req_valid = ids < qlens                                  # prefix mask
    with jax.named_scope("ess.pool"):
        flat_ids = ids.reshape(B, Q * K)
        flat_valid = req_valid.reshape(B, Q * K)
        # one query's top-k is duplicate-free; only the Q>1 flattening can
        # request the same position twice (skip the O(K^2) dedup at Q=1)
        pool, lk, stats = LP.lookup(state.pool, flat_ids, flat_valid, M_env,
                                    slot_mask=slot_mask, dedup=Q > 1)
    return pool, lk, stats, ids, req_valid, K, M_env, sc


def _finish_attention(mla_p, cfg, x_norm, positions, pool, lk, ids,
                      req_valid, fetched, K, M_env, overlap, use_kernel,
                      slot_mask):
    """Attention + LRU admission over already-resolved miss rows: Attn0
    on pool-resident rows ∥ Attn1 on ``fetched`` with the exact partial
    merge (or one union attention for ``overlap="none"``).  Shared by the
    synchronous gather path and the staged-slab path — they differ only
    in where ``fetched`` came from, so value-identical sourcing gives
    bit-identical outputs (the async-offload parity bar).  Returns
    ``(out, pool-after-admit)``; the caller ticks the clock."""
    B, Q, _ = x_norm.shape
    with jax.named_scope("ess.attend"):
        q_comb = M.absorbed_query(mla_p, cfg, x_norm, positions)  # [B,Q,H,D]
        hit = lk.hit.reshape(B, Q, K)
        if overlap == "none":
            # single attention over the union: every row depends on the
            # fetch
            with jax.named_scope("ess.pool"):
                rows_hit, _ = LP.gather_resident(pool, lk.slot, lk.hit)
            # misses: place fetched rows back at their request positions
            fr = jnp.where(lk.miss_rank[..., None] < M_env,
                           jnp.take_along_axis(
                               fetched, jnp.clip(lk.miss_rank, 0, M_env - 1)
                               [..., None], axis=1), 0)
            rows = jnp.where(lk.hit[..., None], rows_hit, fr)
            valid = (lk.hit | (lk.miss_rank < M_env)) & \
                (ids.reshape(B, Q * K) >= 0)
            part = _attend_rows(q_comb, rows.reshape(B, Q, K, -1),
                                valid.reshape(B, Q, K), cfg, use_kernel)
        else:
            # Attn0: pool-resident rows only (independent of the fetch)
            with jax.named_scope("ess.pool"):
                rows0, _ = LP.gather_resident(pool, lk.slot, lk.hit)
            p0 = _attend_rows(q_comb, rows0.reshape(B, Q, K, -1),
                              hit & req_valid.reshape(B, Q, K).astype(bool),
                              cfg, use_kernel)
            # Attn1: fetched rows (waits on the H2D copy); at Q>1 each
            # query attends only the rows it requested (at Q=1 that set is
            # exactly the whole miss buffer — skip the scatter)
            mvalid = (lk.miss_ids >= 0)
            fvalid = _fetch_valid(lk, B, Q, K, M_env) & mvalid[:, None] \
                if Q > 1 else jnp.broadcast_to(mvalid[:, None],
                                               (B, Q, M_env))
            p1 = _attend_rows(q_comb, fetched[:, None].repeat(Q, 1)
                              if Q > 1 else fetched[:, None],
                              fvalid, cfg, use_kernel)
            part = M.merge_partials(p0, p1)

        out_lat = M.finalize_partial(part, x_norm.dtype)
        out = M.output_proj(mla_p, cfg, out_lat)

    with jax.named_scope("ess.pool"):
        pool = LP.admit(pool, lk.miss_ids, fetched, slot_mask=slot_mask)
    return out, pool


def _da_or_none(mla_p, idx_p, cfg, x_norm, positions, state, idx_keys, lens,
                overlap, use_kernel, slot_mask=None):
    pool, lk, stats, ids, req_valid, K, M_env, _ = _topk_and_lookup(
        idx_p, cfg, x_norm, state, idx_keys, lens, slot_mask)

    # ---- issue the H2D fetch as early as possible (DA overlap) ----
    with jax.named_scope("ess.miss_gather"):
        fetched = offload.gather_tier_rows(
            state.host_latent, state.host_scales, lk.miss_ids,
            layer=state.layer, batch_offset=state.batch_offset,
            block_table=state.block_table)

    out, pool = _finish_attention(mla_p, cfg, x_norm, positions, pool, lk,
                                  ids, req_valid, fetched, K, M_env,
                                  overlap, use_kernel, slot_mask)
    with jax.named_scope("ess.pool"):
        pool = LP.tick(pool)
    new_state = state._replace(pool=pool)
    return out, new_state, ESSStats(stats.hits, stats.misses, stats.overflow)


def ess_sparse_attention_staged(mla_p: dict, idx_p: dict, cfg: ArchConfig,
                                x_norm: jax.Array, positions: jax.Array,
                                state: ESSLayerState, idx_keys: jax.Array,
                                lens: jax.Array, *, new_rows: jax.Array,
                                widx: jax.Array, staged_ids_l: jax.Array,
                                staged_rows_l: jax.Array,
                                staged_scales_l: jax.Array | None = None,
                                overlap: str = "da",
                                use_kernel: bool = False,
                                slot_mask: jax.Array | None = None):
    """One layer of ESS decode attention sourcing miss rows from the
    async-offload staging slab instead of a synchronous host gather (the
    pipeline's compute stage).

    The *selection* semantics (indexer scores, top-K, pool lookup, miss
    buffer, LRU admission) are exactly :func:`ess_sparse_attention`'s —
    only row *sourcing* changes, resolved in precedence order:

    1. **own-row bypass** — the round's freshly appended latents
       (``new_rows [B,Q,D]`` at positions ``widx [B,Q]``) are still in
       the spill slab (their D2H is deferred to the commit stage), so a
       miss on them is served from the live activations.  Bit-identical
       to the synchronous host round trip: the scatter stores
       ``astype(host dtype)`` and the gather reads it back verbatim.
    2. **staged-slab match** — rows predicted and prefetched during the
       *previous* round (``staged_ids_l/staged_rows_l [B,P(,D)]``).
    3. **synchronous fallback** — mispredicted misses gather from the
       host tier under a nested ``lax.cond``: a fully-predicted round
       keeps the H2D path off the critical graph entirely.

    The whole sourcing block sits under one ``lax.cond`` on the round
    having any valid miss at all: a steady-state round whose top-K is
    fully pool-resident pays a single skipped branch instead of the
    per-layer match machinery (the plan stage rides *every* round, so
    its cost bounds the pipeline's overhead floor — which is also why
    the planning inputs are returned to the round driver and ranked
    once, batched across layers, rather than per layer here).

    Returns ``(out, new_state, stats, plan_sig, (hits, unmatched) [B]
    each)`` — ``plan_sig = (sc_last [B,S], qlens_last [B], slot_of
    [B,S])`` is this layer's plan-stage signal (last query's indexer
    scores, its horizon, post-admit pool residency); the counters are
    gated on ``slot_mask`` so frozen slots contribute zero.
    ``overlap="dba"`` degrades to the DA graph (the slab already
    decouples the fetch the batch-split indexer would have hidden)."""
    from repro.core import transfer as TR
    B, Q, _ = x_norm.shape
    live = jnp.ones((B,), bool) if slot_mask is None else slot_mask
    pool, lk, stats, ids, req_valid, K, M_env, sc = _topk_and_lookup(
        idx_p, cfg, x_norm, state, idx_keys, lens, slot_mask)

    with jax.named_scope("ess.miss_gather"):
        mvalid = lk.miss_ids >= 0
    D = new_rows.shape[-1]

    def _source_rows():
        own_eq = (lk.miss_ids[:, :, None] == widx[:, None, :]) \
            & (widx >= 0)[:, None, :]                            # [B,M,Q]
        own = own_eq.any(-1)
        own_rows = jnp.take_along_axis(
            new_rows, jnp.argmax(own_eq, -1)[:, :, None], axis=1)  # [B,M,D]
        need = mvalid & ~own
        smatch, srows = TR.match_staged(staged_ids_l, staged_rows_l,
                                        lk.miss_ids, need,
                                        staged_scales_l=staged_scales_l,
                                        out_dtype=new_rows.dtype)
        unmatched = need & ~smatch
        fb_ids = jnp.where(unmatched, lk.miss_ids, -1)
        fb = jax.lax.cond(
            jnp.any(unmatched),
            lambda: offload.gather_tier_rows(state.host_latent,
                                             state.host_scales, fb_ids,
                                             layer=state.layer,
                                             batch_offset=state.batch_offset,
                                             block_table=state.block_table,
                                             out_dtype=new_rows.dtype),
            lambda: jnp.zeros((B, M_env, D), new_rows.dtype))
        fetched = jnp.where(own[..., None], own_rows,
                            jnp.where(smatch[..., None], srows, fb))
        return (jnp.where(mvalid[..., None], fetched, 0),
                smatch.sum(-1).astype(jnp.int32),
                unmatched.sum(-1).astype(jnp.int32))

    with jax.named_scope("ess.miss_gather"):
        fetched, s_hits, s_unm = jax.lax.cond(
            jnp.any(mvalid), _source_rows,
            lambda: (jnp.zeros((B, M_env, D), new_rows.dtype),
                     jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)))

    out, pool = _finish_attention(mla_p, cfg, x_norm, positions, pool, lk,
                                  ids, req_valid, fetched, K, M_env,
                                  "da" if overlap == "dba" else overlap,
                                  use_kernel, slot_mask)
    with jax.named_scope("ess.pool"):
        pool = LP.tick(pool)

    # this layer's plan-stage signal and prefetch counters
    with jax.named_scope("ess.prefetch"):
        qlast = lens[:, -1] if lens.ndim == 2 else lens
        liv = live.astype(jnp.int32)
        sig = (sc[:, -1], qlast, pool.slot_of)
        pf = (s_hits * liv, s_unm * liv)
    return out, state._replace(pool=pool), \
        ESSStats(stats.hits, stats.misses, stats.overflow), sig, pf


def _dba(mla_p, idx_p, cfg, x_norm, positions, state, idx_keys, lens,
         use_kernel, slot_mask=None):
    """DualBatch-Attention: batch split in two, indexer of half-2 overlaps
    the fetch of half-1."""
    B = x_norm.shape[0]
    h = B // 2
    if h == 0:
        return _da_or_none(mla_p, idx_p, cfg, x_norm, positions, state,
                           idx_keys, lens, "da", use_kernel, slot_mask)
    sm0 = None if slot_mask is None else slot_mask[:h]
    sm1 = None if slot_mask is None else slot_mask[h:]

    def half(sl, off):
        pool = LP.PoolState(*(a[sl] if a.ndim > 0 else a
                              for a in state.pool))
        pool = pool._replace(step=state.pool.step)
        # host cache (and block table) stays whole; the half indexes it
        # via batch_offset
        return ESSLayerState(pool, state.host_latent, state.layer,
                             state.batch_offset + off, state.block_table,
                             state.host_scales)

    s0, s1 = half(slice(0, h), 0), half(slice(h, None), h)
    # half-1 indexer + fetch issue
    p0_pool, lk0, st0, ids0, rv0, K, M_env, _ = _topk_and_lookup(
        idx_p, cfg, x_norm[:h], s0, idx_keys[:h], lens[:h], sm0)
    with jax.named_scope("ess.miss_gather"):
        fetched0 = offload.gather_tier_rows(
            s0.host_latent, s0.host_scales, lk0.miss_ids, layer=s0.layer,
            batch_offset=s0.batch_offset, block_table=s0.block_table)
    # half-2 indexer (independent of fetched0 -> overlaps the copy)
    p1_pool, lk1, st1, ids1, rv1, _, _, _ = _topk_and_lookup(
        idx_p, cfg, x_norm[h:], s1, idx_keys[h:], lens[h:], sm1)
    with jax.named_scope("ess.miss_gather"):
        fetched1 = offload.gather_tier_rows(
            s1.host_latent, s1.host_scales, lk1.miss_ids, layer=s1.layer,
            batch_offset=s1.batch_offset, block_table=s1.block_table)

    out0, ns0 = _finish_half(mla_p, cfg, x_norm[:h], positions[:h], p0_pool,
                             lk0, ids0, rv0, fetched0, s0, K, M_env,
                             use_kernel, sm0)
    out1, ns1 = _finish_half(mla_p, cfg, x_norm[h:], positions[h:], p1_pool,
                             lk1, ids1, rv1, fetched1, s1, K, M_env,
                             use_kernel, sm1)

    pool = LP.PoolState(*(jnp.concatenate([a, b], 0) if a.ndim > 0 else a
                          for a, b in zip(ns0.pool, ns1.pool)))
    pool = pool._replace(step=state.pool.step)
    with jax.named_scope("ess.pool"):
        pool = LP.tick(pool)
    with jax.named_scope("ess.attend"):
        out = jnp.concatenate([out0, out1], 0)
    with jax.named_scope("ess.pool"):
        hits = jnp.concatenate([st0.hits, st1.hits], 0)
        misses = jnp.concatenate([st0.misses, st1.misses], 0)
        ovf = jnp.concatenate([st0.overflow, st1.overflow], 0)
    return out, state._replace(pool=pool), ESSStats(hits, misses, ovf)


def _finish_half(mla_p, cfg, x_norm, positions, pool, lk, ids, req_valid,
                 fetched, st, K, M_env, use_kernel, slot_mask=None):
    B, Q, _ = x_norm.shape
    with jax.named_scope("ess.attend"):
        q_comb = M.absorbed_query(mla_p, cfg, x_norm, positions)
        hit = lk.hit.reshape(B, Q, K)
        with jax.named_scope("ess.pool"):
            rows0, _ = LP.gather_resident(pool, lk.slot, lk.hit)
        p0 = _attend_rows(q_comb, rows0.reshape(B, Q, K, -1),
                          hit & req_valid.astype(bool), cfg, use_kernel)
        mvalid = lk.miss_ids >= 0
        fvalid = _fetch_valid(lk, B, Q, K, M_env) & mvalid[:, None] \
            if Q > 1 else jnp.broadcast_to(mvalid[:, None], (B, Q, M_env))
        p1 = _attend_rows(q_comb, fetched[:, None].repeat(Q, 1) if Q > 1
                          else fetched[:, None],
                          fvalid, cfg, use_kernel)
        part = M.merge_partials(p0, p1)
        out = M.output_proj(mla_p, cfg,
                            M.finalize_partial(part, x_norm.dtype))
    with jax.named_scope("ess.pool"):
        pool = LP.admit(pool, lk.miss_ids, fetched, slot_mask=slot_mask)
    return out, st._replace(pool=pool)
