"""Serving engine: prefill + decode step functions.

Two decode paths:

* **generic** — any arch via ``repro.models.transformer.forward`` (contiguous
  caches, monolithic attention).
* **ESS** — DSA+MLA archs with ``cfg.ess.enabled``: unrolled layer loop so
  every layer's host fetch / Attn0 / Attn1 dependence structure stays
  visible to the XLA scheduler (DA/DBA overlap, paper §3.3).  Per layer:

    1. ln1 → new latent entry + indexer key appended (device ikeys;
       host_latent via D2H writeback — Figure 3's small D2H),
    2. ``ess_sparse_attention`` (fetch → Attn0 ∥ copy → Attn1 → exact merge,
       LRU admit),
    3. residual + (dense | MoE) ffn.

Prefill runs the chunked DSA path, scatters the latents to the host tier
(the PD-disaggregation "Load" arrow in Figure 3) and applies LRU-Warmup.

The serving stack is split across four modules:

* this one — the model step functions (``ess_decode`` /
  ``ess_prefill_chunk``) and the host-side :class:`ServeSession`
  **re-entrant engine core**: ``step_round()`` runs exactly one serve
  round (admissions → one prefill chunk → one decode/verify step) and
  returns the round's :class:`~repro.serving.api.TokenEvent` batch —
  requests can be submitted and aborted between any two rounds, EOS /
  stop tokens truncate *within* a speculative round (the over-accepted
  suffix's lens/pool state is rolled back), and every request ends with
  exactly one terminal event (``stop | length | abort | rejected |
  budget``).  ``run()`` survives as a thin run-to-completion compat
  shim over the same core, with bit-identical streams;
* :mod:`repro.serving.api` — the public front-end (``EssEngine`` with
  ``submit`` / ``step`` / ``stream`` / ``generate`` / ``abort`` /
  ``metrics``, ``SamplingParams``, ``TokenEvent``, ``RequestOutput``);
* :mod:`repro.serving.state` — the device-resident ``EngineState``
  pytree a round consumes and produces;
* :mod:`repro.serving.step` — the ``StepProgram`` builder that compiles
  each round kind (decode / MTP draft+verify / prefill chunk) into one
  donated jit program with in-device token selection.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.cache import latent_cache as LC
from repro.configs.base import ArchConfig
from repro.core import lru_pool as LP
from repro.core import offload, warmup
from repro.core import transfer as TR
from repro.core.overlap import (ESSLayerState, _attend_rows,
                                ess_sparse_attention,
                                ess_sparse_attention_staged)
from repro.distributed import compression as cmp
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import mla as M
from repro.models import moe as MoE
from repro.models import transformer as T
from repro.serving import state as ES
from repro.serving import step as SP
from repro.serving.api import TokenEvent
from repro.serving.sampling import greedy, request_key, sample
from repro.serving.scheduler import Request, Scheduler


class DecodeOut(NamedTuple):
    logits: jax.Array
    caches: Any
    stats: dict


# ---------------------------------------------------------------------------
# Generic path
# ---------------------------------------------------------------------------

def generic_prefill(params, cfg: ArchConfig, tokens, positions, **kw):
    return T.forward(params, cfg, tokens, positions, mode="prefill", **kw)


def generic_decode(params, cfg: ArchConfig, tokens, positions, caches, **kw):
    out = T.forward(params, cfg, tokens, positions, mode="decode",
                    caches=caches, **kw)
    return DecodeOut(out.logits, out.caches, {})


# ---------------------------------------------------------------------------
# ESS path (DSA + MLA + offload)
# ---------------------------------------------------------------------------

class _LayerParams:
    """One layer's slice of the stacked weights, cut group by group on
    first use, so that each slice carries the ``ess.*`` scope of the
    stage that reads it."""

    def __init__(self, stack: dict, index: int):
        self._stack, self._index, self._cut = stack, index, {}

    def __getitem__(self, key: str):
        if key not in self._cut:
            self._cut[key] = jax.tree.map(lambda a: a[self._index],
                                          self._stack[key])
        return self._cut[key]


def _layer_params(params, cfg: ArchConfig, layer: int):
    nd = cfg.moe.first_dense_layers if cfg.moe else 0
    if layer < nd:
        return _LayerParams(params["dense_layers"], layer), False
    return _LayerParams(params["layers"], layer - nd), cfg.moe is not None


def _overlap_for_layer(cfg: ArchConfig, layer: int,
                       layerwise: tuple[str, ...] | None) -> str:
    if cfg.ess.overlap == "layerwise":
        if layerwise is not None:
            return layerwise[layer]
        return "da"
    return cfg.ess.overlap


def ess_decode(params, cfg: ArchConfig, tokens, positions,
               caches: LC.ESSCaches, *, use_kernel: bool = False,
               layerwise_policy: tuple[str, ...] | None = None,
               slot_mask: jax.Array | None = None,
               staged: tuple[jax.Array, jax.Array] | None = None
               ) -> DecodeOut:
    """tokens [B,Q] -> logits [B,Q,V].  Q>1 = MTP draft verification.

    ``slot_mask`` [B] bool marks the live decode slots of a continuous
    batch.  Masked slots are gated *inside* the step: their host scatter
    and indexer-cache append are dropped, their pool takes no lookups or
    admissions, and their ``lens`` do not advance.  Without in-step gating
    a freed (or still-prefilling) slot runs a phantom step — its stale
    block table can alias a live slot's physical host page and its pool
    silently admits a garbage latent row that a future occupant then
    *hits* on.

    ``staged`` switches the step into the **pipelined** round shape
    (plan → compute → commit, the async-offload tentpole): it carries the
    previous round's staging slab pair ``(staged_ids [L,B,P],
    staged_rows [L,B,P,D])``.  The compute stage then sources miss rows
    from the slab (:func:`repro.core.overlap.ess_sparse_attention_staged`
    — own-round bypass, slab match, cond-gated sync fallback), the
    per-layer D2H spill of new latents is deferred into **one** stacked
    commit-stage scatter after the layer loop, and the plan stage gathers
    next round's predicted rows into a fresh slab *after* that commit (so
    the predictions may include this round's appends).  The stats dict
    gains ``staged_ids`` / ``staged_rows`` (the next slab) and
    ``pf_hits`` / ``pf_misses`` / ``pf_wasted`` ``[B]`` prefetch
    counters.  ``staged=None`` is the synchronous path, bit-identical to
    the pre-pipeline graph.
    """
    B, Q = tokens.shape
    with jax.named_scope("ess.embed"):
        x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
        x = shard(x, "batch", None, "embed_act")
        lens = caches.lens
        if slot_mask is None:
            live = jnp.ones((B,), bool)
        else:
            live = slot_mask
        new_lens = lens + Q * live.astype(lens.dtype)
        bi = jnp.arange(B)[:, None]
        widx = jnp.where(live[:, None],
                         lens[:, None] + jnp.arange(Q)[None, :], -1)  # [B,Q]
        # per-query attention horizon: draft q sees positions <= its own
        # (the Q window stays causal — without this every draft would
        # attend to entries appended by later drafts, breaking parity with
        # sequential Q=1 steps); masked slots contribute no valid entries
        attn_lens = widx + 1                                      # [B,Q]

    host_latent = caches.host_latent
    host_scales = caches.host_scales   # per-row scales of a quantized tier
    ikeys_all = caches.ikeys
    pools = caches.pools
    with jax.named_scope("ess.pool"):
        hits = misses = ovf = jnp.zeros((B,), jnp.int32)
    lat_stack: list[jax.Array] = []    # staged mode: deferred D2H spill
    scale_stack: list[jax.Array] = []  # staged+quantized: the rows' scales
    plan_sigs: list[tuple] = []        # staged mode: per-layer plan signal
    with jax.named_scope("ess.prefetch"):
        pf_h = pf_m = pf_w = jnp.zeros((B,), jnp.int32)

    for layer in range(cfg.num_layers):
        lp, is_moe = _layer_params(params, cfg, layer)
        with jax.named_scope("ess.attend"):
            h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)

        # --- append: indexer key (device) + latent entry (host, D2H) -----
        with jax.named_scope("ess.indexer"):
            new_ik = M.indexer_keys(lp["indexer"], h)            # [B,Q,Di]
            S_ik = ikeys_all[layer].shape[1]
            ik_widx = jnp.where(widx >= 0, widx, S_ik)           # OOB -> drop
            ik_l = ikeys_all[layer].at[bi, ik_widx].set(
                new_ik.astype(ikeys_all[layer].dtype), mode="drop")
        ikeys_all = ikeys_all[:layer] + (ik_l,) + ikeys_all[layer + 1:]
        with jax.named_scope("ess.attend"):
            new_lat = M.latent_entries(lp["mla"], cfg, h, positions)
        with jax.named_scope("ess.spill"):
            if staged is None:
                # masked slots' gating is already folded into widx (-1
                # drops)
                host_latent, host_scales = offload.scatter_tier_rows(
                    host_latent, host_scales, widx, new_lat, slot_mask=None,
                    layer=layer, block_table=caches.block_tables)
            elif host_scales is None:
                # pipelined: spill deferred to the commit stage (one
                # stacked scatter after the loop); keep the host-dtype rows
                # at hand so same-round misses are served from the live
                # activations
                lat_stack.append(new_lat.astype(host_latent.dtype))
                own_rows = lat_stack[-1]
            else:
                # pipelined + quantized: quantize ONCE here and commit the
                # exact (q, s) pair later — the own-row bypass serves
                # dequant(q, s), which is bit-identical to the synchronous
                # scatter→gather round trip (re-quantizing dequantized
                # rows would land on a different grid point)
                q_lat, s_lat = cmp.quantize_rows(new_lat, host_latent.dtype)
                lat_stack.append(q_lat)
                scale_stack.append(s_lat)
                own_rows = cmp.dequantize_rows(q_lat, s_lat, cfg.param_dtype)

        # --- ESS sparse attention (fetch ∥ Attn0, Attn1, merge, admit) ---
        # (core.overlap scopes its own stages: indexer, topk, pool,
        # miss_gather, attend)
        st = ESSLayerState(pools[layer], host_latent, layer,
                           block_table=caches.block_tables,
                           host_scales=host_scales)
        ov = _overlap_for_layer(cfg, layer, layerwise_policy)
        if staged is None:
            attn, st2, stats = ess_sparse_attention(
                lp["mla"], lp["indexer"], cfg, h, positions, st, ik_l,
                attn_lens, overlap=ov, use_kernel=use_kernel,
                slot_mask=live)
        else:
            with jax.named_scope("ess.miss_gather"):
                sc_l = None if len(staged) < 3 or staged[2] is None \
                    else staged[2][layer]
                ids_l, rows_l = staged[0][layer], staged[1][layer]
            attn, st2, stats, sig, pf = ess_sparse_attention_staged(
                lp["mla"], lp["indexer"], cfg, h, positions, st, ik_l,
                attn_lens, new_rows=own_rows, widx=widx,
                staged_ids_l=ids_l, staged_rows_l=rows_l,
                staged_scales_l=sc_l, overlap=ov,
                use_kernel=use_kernel, slot_mask=live)
            plan_sigs.append(sig)
            with jax.named_scope("ess.prefetch"):
                pf_h, pf_m = pf_h + pf[0], pf_m + pf[1]
        pools = pools[:layer] + (st2.pool,) + pools[layer + 1:]
        with jax.named_scope("ess.attend"):
            x = x + attn

        # --- ffn ----------------------------------------------------------
        with jax.named_scope("ess.ffn"):
            h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            if is_moe:
                f, _ = MoE.moe_apply(lp["ffn"], cfg, h2)
            else:
                f = L.mlp(lp["ffn"], h2, cfg.act)
            x = x + f
        with jax.named_scope("ess.pool"):
            hits = hits + stats.hits
            misses = misses + stats.misses
            ovf = ovf + stats.overflow

    with jax.named_scope("ess.head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = L.unembed(params.get("unembed", params.get("embed")), x,
                           cap=cfg.logit_softcap)
    stats_out = {"hits": hits, "misses": misses, "overflow": ovf,
                 "hidden": x}
    if staged is not None:
        # --- commit stage: one stacked D2H spill of the round's appends
        # (quantized tier: the layer loop's precomputed (q, s) pairs land
        # verbatim — payload and scale plane in one stacked scatter each,
        # so the PCIe bytes stay at compressed width) -----------------
        with jax.named_scope("ess.spill"):
            host_latent = offload.scatter_from_slab(
                host_latent, widx, jnp.stack(lat_stack), slot_mask=None,
                block_table=caches.block_tables)
            if host_scales is not None:
                host_scales = offload.scatter_from_slab(
                    host_scales, widx, jnp.stack(scale_stack),
                    slot_mask=None, block_table=caches.block_tables)
        # --- plan stage: stage next round's predicted rows (after the
        # commit, so predictions may target rows appended this round).
        # The whole plan is gated on the round having *missed at all*: a
        # zero-miss round proves residency covered the working set, so
        # the freshest plan is the one already staged — the slab passes
        # through untouched and the steady-state round pays one skipped
        # cond instead of a top-k + gather.  Rounds that did miss rank
        # the per-layer signals in one batched top-k rather than L
        # separate ones ------------------------------------------------
        Lh, P = staged[0].shape[0], staged[0].shape[2]
        st_scales = staged[2] if len(staged) > 2 else None

        def _plan():
            sc_all = jnp.stack([s[0] for s in plan_sigs])         # [L,B,S]
            so_all = jnp.stack([s[2] for s in plan_sigs])         # [L,B,S]
            pred = TR.plan_prefetch(
                sc_all.reshape(Lh * B, -1), jnp.tile(plan_sigs[0][1], Lh),
                so_all.reshape(Lh * B, -1), jnp.tile(live, Lh),
                cfg.dsa.index_topk, P).reshape(Lh, B, P)          # [L,B,P]
            # rows already staged last round are reused in place:
            # committed host rows are append-only below the truncation
            # edges, and every truncation edge cancels the staged ids it
            # invalidates, so a surviving id's bytes cannot have changed.
            # Only genuinely new ids touch the link — a plan that
            # re-predicts a stable margin skips the H2D gather entirely.
            # A quantized tier's scale plane shadows the rows exactly
            # (same reuse select, same slab gather at one byte-pair per
            # row extra) so the staged pair always dequantizes
            # coherently.
            old_ids, old_rows = staged[0], staged[1]
            eq = (pred[..., None] == old_ids[..., None, :]) \
                & (old_ids >= 0)[..., None, :] & (pred >= 0)[..., None]
            have = eq.any(-1)                                     # [L,B,P]
            src = jnp.argmax(eq, axis=-1)
            reused = jnp.take_along_axis(old_rows, src[..., None], axis=2)
            new_ids = jnp.where(have, -1, pred)

            def _gather():
                rows = offload.gather_into_slab(
                    host_latent, new_ids, slot_mask=None,
                    block_table=caches.block_tables)
                if st_scales is None:
                    return (rows,)
                return rows, offload.gather_into_slab(
                    host_scales, new_ids, slot_mask=None,
                    block_table=caches.block_tables)

            def _zeros():
                if st_scales is None:
                    return (jnp.zeros_like(old_rows),)
                return jnp.zeros_like(old_rows), jnp.zeros_like(st_scales)

            fresh = jax.lax.cond(jnp.any(new_ids >= 0), _gather, _zeros)
            rows_out = jnp.where(have[..., None], reused, fresh[0])
            if st_scales is None:
                return pred, rows_out
            reused_s = jnp.take_along_axis(st_scales, src[..., None],
                                           axis=2)
            return pred, rows_out, jnp.where(have[..., None], reused_s,
                                             fresh[1])

        keep = (lambda: (staged[0], staged[1])) if st_scales is None \
            else (lambda: (staged[0], staged[1], st_scales))
        with jax.named_scope("ess.prefetch"):
            plan_out = jax.lax.cond(jnp.any(misses > 0), _plan, keep)
            pf_w = ((staged[0] >= 0).sum((0, 2)).astype(jnp.int32)
                    * live.astype(jnp.int32) - pf_h)
        pred, slab_rows = plan_out[0], plan_out[1]
        stats_out.update(staged_ids=pred, staged_rows=slab_rows,
                         pf_hits=pf_h, pf_misses=pf_m, pf_wasted=pf_w)
        if st_scales is not None:
            stats_out["staged_scales"] = plan_out[2]
    new_caches = caches._replace(lens=new_lens, host_latent=host_latent,
                                 host_scales=host_scales,
                                 ikeys=ikeys_all, pools=pools)
    return DecodeOut(logits, new_caches, stats_out)


def ess_prefill_chunk(params, cfg: ArchConfig, tokens, positions,
                      caches: LC.ESSCaches, *, slot=None,
                      want_logits: bool = True, collect_tail: int = 0,
                      use_kernel: bool = False,
                      n_valid: jax.Array | int | None = None
                      ) -> tuple[Optional[jax.Array], LC.ESSCaches, tuple,
                                 Optional[jax.Array]]:
    """One chunked-prefill step: ``tokens [B,C]`` continue the sequence(s)
    at ``caches.lens`` and their latents/indexer keys land **directly in
    the already-mapped host pages** — no donor cache, no graft.

    * ``slot`` restricts the step to one decode slot of a shared
      continuous-batching cache (``None`` = all ``B`` rows, the compat
      :func:`ess_prefill` path).  It may be a traced i32 scalar: the
      compiled serve round passes the admitting slot dynamically so one
      program covers every slot.
    * ``n_valid`` (scalar, may be traced) marks the first ``n_valid``
      chunk positions as real; the rest are padding that a shape-bucketed
      ragged final chunk carries.  Pad positions write nothing (host
      scatter and indexer-cache appends dropped, ``lens`` advance by
      ``n_valid``), are never attended by valid queries (their ``widx``
      is ``-1``, so the causal mask excludes them), and their own
      outputs are finite garbage that is discarded.  Because pad tokens
      sit *after* every valid token, the MoE capacity cumsum assigns
      valid tokens the same expert slots as an unpadded run — valid
      positions are bit-identical to the unpadded chunk (as long as no
      token hits the capacity clip, the same assumption the chunked ==
      one-shot parity already rests on).
    * Attention is the exact causal DSA selection: per-query Top-K over the
      slot's indexer cache, prior-context rows fetched from the host tier,
      intra-chunk rows served from the chunk itself (they are D2H'd once,
      *after* the layer loop, via one stacked scatter per chunk).
    * The Sparse Memory Pool is untouched — prefill runs on the
      bandwidth-rich side of the PD split; LRU-Warmup is replayed
      separately after the last chunk.
    * Per-token outputs are invariant to the chunking (fixed-shape score /
      gather / attend stages), so any ``prefill_chunk`` is bit-identical
      to the one-shot path.

    Returns ``(logits|None, caches, tails, hidden_last)`` where ``tails``
    holds each layer's post-ln1 hidden states for the last
    ``collect_tail`` chunk positions (LRU-Warmup replay input) and
    ``hidden_last`` is the post-final-norm hidden at the chunk's last
    position (``None`` unless ``want_logits`` — the MTP draft seed when
    a slot promotes from prefill to speculative decode).
    """
    if slot is None:
        b0, Bc = 0, tokens.shape[0]
    else:
        b0, Bc = slot, 1
    C = tokens.shape[1]
    host = caches.host_latent
    ikeys_all = caches.ikeys
    S = ikeys_all[0].shape[1]
    K = min(cfg.dsa.index_topk, S)
    with jax.named_scope("ess.embed"):
        start = jax.lax.dynamic_slice_in_dim(caches.lens, b0, Bc)  # [Bc]
        x = L.embed(params["embed"], tokens).astype(cfg.param_dtype)
        x = shard(x, "batch", None, "embed_act")
        bi = jnp.arange(Bc)[:, None]
        nv = jnp.asarray(C if n_valid is None else n_valid, jnp.int32)
        cpos = jnp.arange(C, dtype=jnp.int32)
        widx = jnp.where(cpos[None, :] < nv,
                         start[:, None] + cpos[None, :], -1)     # [Bc,C]
        causal = jnp.arange(S)[None, None, :] <= widx[:, :, None]  # [Bc,C,S]
    lat_stack = []
    scale_stack = []           # quantized tier: the chunk rows' scales
    tails = []

    for layer in range(cfg.num_layers):
        lp, is_moe = _layer_params(params, cfg, layer)
        with jax.named_scope("ess.attend"):
            h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        if collect_tail:
            tails.append(h[:, -collect_tail:])

        # --- append indexer keys (device) + chunk latents (deferred D2H) --
        with jax.named_scope("ess.indexer"):
            ik_full = ikeys_all[layer]
            ik_slot = jax.lax.dynamic_slice_in_dim(ik_full, b0, Bc, axis=0)
            new_ik = M.indexer_keys(lp["indexer"], h)            # [Bc,C,Di]
            ik_slot = ik_slot.at[bi, jnp.where(widx >= 0, widx, S)].set(
                new_ik.astype(ik_slot.dtype), mode="drop")
            ik_full = jax.lax.dynamic_update_slice_in_dim(ik_full, ik_slot,
                                                          b0, axis=0)
        ikeys_all = ikeys_all[:layer] + (ik_full,) + ikeys_all[layer + 1:]
        with jax.named_scope("ess.attend"):
            new_lat = M.latent_entries(lp["mla"], cfg, h, positions)
        with jax.named_scope("ess.spill"):
            if caches.host_scales is None:
                new_lat = new_lat.astype(host.dtype)
                lat_stack.append(new_lat)
            else:
                # quantize ONCE: the (q, s) pair is what the deferred
                # stacked scatter commits, and intra-chunk attention
                # serves dequant(q, s) — the same value any *cross*-chunk
                # query reads back from the tier, so chunked == one-shot
                # parity survives quantization
                q_lat, s_lat = cmp.quantize_rows(new_lat, host.dtype)
                lat_stack.append(q_lat)
                scale_stack.append(s_lat)
                new_lat = cmp.dequantize_rows(q_lat, s_lat, cfg.param_dtype)

        # --- exact causal DSA: per-query Top-K over the slot's keys ------
        with jax.named_scope("ess.indexer"):
            iq = M.indexer_query(lp["indexer"], h)
            sc = M.indexer_scores(iq, ik_slot)                   # [Bc,C,S]
        with jax.named_scope("ess.topk"):
            ids = M.topk_ids(sc, K, causal)                      # [Bc,C,K]
            req_valid = ids <= widx[:, :, None]                  # prefix mask
        # prior context from host pages; intra-chunk rows from the chunk
        with jax.named_scope("ess.miss_gather"):
            local = ids >= start[:, None, None]
            prior_ids = jnp.where(local, -1, ids)
            rows_h = offload.gather_tier_rows(
                host, caches.host_scales, prior_ids.reshape(Bc, C * K),
                layer=layer, batch_offset=b0,
                block_table=caches.block_tables,
                out_dtype=new_lat.dtype).reshape(Bc, C, K, -1)
            loc = jnp.clip(ids - start[:, None, None], 0, C - 1)
            rows_l = jnp.take_along_axis(new_lat[:, None], loc[..., None],
                                         axis=2)                 # [Bc,C,K,D]
            rows = jnp.where(local[..., None], rows_l, rows_h)

        with jax.named_scope("ess.attend"):
            q_comb = M.absorbed_query(lp["mla"], cfg, h, positions)
            # fp32 attend (prefill runs on the compute-rich side): matches
            # the monolithic prefill/train references' softmax precision,
            # so the selection sets of deeper layers don't drift across
            # near-ties
            part = _attend_rows(q_comb.astype(jnp.float32),
                                rows.astype(jnp.float32), req_valid, cfg,
                                use_kernel=use_kernel)
            attn = M.output_proj(lp["mla"], cfg,
                                 M.finalize_partial(part, x.dtype))
            x = x + attn

        # --- ffn ----------------------------------------------------------
        with jax.named_scope("ess.ffn"):
            h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            if is_moe:
                f, _ = MoE.moe_apply(lp["ffn"], cfg, h2)
            else:
                f = L.mlp(lp["ffn"], h2, cfg.act)
            x = x + f

    # one stacked D2H scatter for the whole chunk (all layers, same rows;
    # pad rows carry widx == -1 and are dropped).  Quantized tier: payload
    # and scale plane each take one stacked scatter of the precomputed
    # (q, s) pairs — compressed D2H width
    with jax.named_scope("ess.spill"):
        host = offload.host_scatter_rows_stacked(
            host, widx, jnp.stack(lat_stack), slot_mask=None,
            batch_offset=b0, block_table=caches.block_tables)
        host_scales = caches.host_scales
        if host_scales is not None:
            host_scales = offload.host_scatter_rows_stacked(
                host_scales, widx, jnp.stack(scale_stack), slot_mask=None,
                batch_offset=b0, block_table=caches.block_tables)
        new_lens = jax.lax.dynamic_update_slice(
            caches.lens, start + nv, (b0,))
    logits = None
    hidden_last = None
    if want_logits:
        with jax.named_scope("ess.head"):
            xf = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = L.unembed(params.get("unembed", params.get("embed")),
                               xf, cap=cfg.logit_softcap)
            hidden_last = xf[:, jnp.maximum(nv - 1, 0)]          # [Bc, d]
    caches = caches._replace(lens=new_lens, host_latent=host,
                             host_scales=host_scales, ikeys=ikeys_all)
    return logits, caches, tuple(tails), hidden_last


def ess_prefill(params, cfg: ArchConfig, tokens, positions, max_seq: int,
                *, do_warmup: bool = True, use_kernel: bool = False,
                prefill_chunk: Optional[int] = None
                ) -> tuple[jax.Array, LC.ESSCaches]:
    """Prefill + LRU-Warmup (paper §3.2) — compat shim over the chunked
    prefill engine.

    The first ``S - W`` tokens stream through :func:`ess_prefill_chunk`
    (one chunk by default, ``prefill_chunk``-sized chunks otherwise —
    bit-identical either way); their latents land in the host-tier Total
    Memory Pool (Figure 3's cross-node "Load").  The last
    ``W = warmup_windows`` tokens are then replayed as scanned
    single-token ESS decode steps: each step computes the true indexer
    Top-2K of its window and LRU-admits the misses — *exactly*
    "sequentially insert the Top-2K IDs of the last W prefill windows
    into the LRU cache"."""
    B, S = tokens.shape
    W = min(cfg.ess.warmup_windows, S - 1) if do_warmup else 0
    Sp = S - W
    caches = LC.init_ess_caches(cfg, B, max_seq, cfg.param_dtype)
    # cap the default chunk: a single Sp-sized chunk materializes
    # O(Sp*K*D) gathered rows + O(Sp*S) score tensors, and chunking is
    # bit-identical anyway
    C = min(Sp, 512) if prefill_chunk is None else max(1, prefill_chunk)
    parts = []
    for c0 in range(0, Sp, C):
        ck = min(C, Sp - c0)
        lg, caches, _, _ = ess_prefill_chunk(
            params, cfg, tokens[:, c0:c0 + ck], positions[:, c0:c0 + ck],
            caches, use_kernel=use_kernel, n_valid=None)
        parts.append(lg)
    logits = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    if W > 0:
        # warmup replays run on the prefill side (bandwidth-rich): use the
        # exact miss envelope (M = K) so outputs match the monolithic model
        # bit-for-bit; the steady-state decode envelope stays provisioned
        # at cfg.ess.max_miss_ratio.
        import dataclasses
        cfg_x = dataclasses.replace(
            cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))

        def step(c, tw):
            tok, pos = tw                                  # [B], [B]
            o = ess_decode(params, cfg_x, tok[:, None], pos[:, None], c,
                           use_kernel=use_kernel, slot_mask=None)
            return o.caches, o.logits[:, 0]

        toks_w = tokens[:, Sp:].T                          # [W, B]
        pos_w = positions[:, Sp:].T
        caches, lg = jax.lax.scan(step, caches, (toks_w, pos_w))
        logits = jnp.concatenate([logits, lg.transpose(1, 0, 2)], axis=1)
    return logits, caches


# ---------------------------------------------------------------------------
# Continuous-batching serve loop (scheduler + paged host tier)
# ---------------------------------------------------------------------------

# depth of the round pipeline: a freshly promoted slot needs this many
# decode rounds before its slab/working set reach steady state (round N
# computes against rows staged in round N-1, which planned off round
# N-2's scores)
PIPELINE_FILL_ROUNDS = 2


@dataclasses.dataclass
class ServeReport:
    rounds: int = 0                     # decode rounds actually stepped
    decode_tokens: int = 0              # tokens emitted by active slots
    prefill_chunks: int = 0             # chunked-prefill steps run
    prefill_tokens: int = 0             # prompt tokens prefilled
    wall_s: float = 0.0
    # wall time spent inside decode rounds only (plan stage -> commit
    # stage of rounds that actually stepped a program).  `rounds_per_s`
    # uses it so admission-only / prefill-only rounds — the pipeline's
    # fill and drain — don't dilute the decode cadence.
    decode_wall_s: float = 0.0
    # decode rounds inside a slot's pipeline-fill window (its first
    # PIPELINE_FILL_ROUNDS rounds after promotion: the slab is empty and
    # the working set cold).  Counted in `rounds` but excluded — numerator
    # *and* denominator — from `rounds_per_s`, identically in sync and
    # overlapped modes, so the cadence compares steady-state rounds only
    # instead of double-counting the pipeline's fill/drain.
    fill_rounds: int = 0
    # async-offload prefetch accounting (summed over layers and slots):
    # staged rows that served misses / misses that fell back to the
    # synchronous gather / staged rows nobody requested
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    prefetch_wasted_rows: int = 0
    # PCIe traffic accounting in *rows*, converted to bytes with the
    # dtype-exact row width (payload + per-row scale for a quantized
    # tier) — the compressed-transfer win shows up here as ~0.5x bytes
    # at identical row counts
    h2d_rows: int = 0                   # miss rows served from the host tier
    d2h_rows: int = 0                   # latent rows written back (all layers)
    host_bytes_per_row: int = 0         # dtype-exact bytes/row of the tier
    finished_rids: list = dataclasses.field(default_factory=list)
    admissions_blocked: int = 0         # admit attempts gated on resources
    peak_pages_in_use: int = 0          # sampled every serve round
    num_pages: int = 0
    ttft_rounds: dict = dataclasses.field(default_factory=dict)
    ttft_s: dict = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)
    # MTP speculative accounting.  With mtp_depth > 0 each round emits a
    # *variable* 1..depth+1 tokens per live slot (accepted drafts + the
    # bonus token), so decode_tokens counts **accepted** tokens —
    # `tokens_per_s` is accepted-tokens/s, while `rounds_per_s` tracks
    # verify-step cadence; the two are equal only at Q=1.
    spec_rounds: int = 0                # rounds run as draft+verify
    drafted_tokens: int = 0             # greedy-slot drafts scored
    accepted_tokens: int = 0            # drafts accepted (excl. bonus)
    # request-lifecycle accounting (public serving API)
    rejected: int = 0                   # oversize/unservable requests
    aborted: int = 0                    # client aborts + budget kills
    finish_reasons: dict = dataclasses.field(default_factory=dict)

    @property
    def tokens_per_s(self) -> float:
        return self.decode_tokens / self.wall_s if self.wall_s > 0 else 0.0

    # alias making the MTP semantics explicit at call sites
    accepted_tokens_per_s = tokens_per_s

    @property
    def rounds_per_s(self) -> float:
        denom = self.decode_wall_s if self.decode_wall_s > 0 else self.wall_s
        return (self.rounds - self.fill_rounds) / denom if denom > 0 else 0.0

    @property
    def prefetch_hit_rate(self) -> float:
        """Staged-row hits / miss-buffer entries needing host rows."""
        tot = self.prefetch_hits + self.prefetch_misses
        return self.prefetch_hits / tot if tot else 0.0

    @property
    def h2d_bytes(self) -> int:
        return self.h2d_rows * self.host_bytes_per_row

    @property
    def d2h_bytes(self) -> int:
        return self.d2h_rows * self.host_bytes_per_row

    @property
    def transfer_bytes_per_round(self) -> float:
        """Mean H2D + D2H bytes per decode round (dtype-exact rows)."""
        return (self.h2d_bytes + self.d2h_bytes) / self.rounds \
            if self.rounds else 0.0

    @property
    def accept_rate(self) -> float:
        """Accepted drafts / drafted tokens (greedy speculative slots)."""
        return self.accepted_tokens / self.drafted_tokens \
            if self.drafted_tokens else 0.0

    @property
    def mean_ttft_s(self) -> float:
        vals = list(self.ttft_s.values())
        return sum(vals) / len(vals) if vals else 0.0


class _RoundPlan(NamedTuple):
    """Output of the round pipeline's plan stage (host-side half)."""
    active: list            # slots stepping this round
    pending: list           # (slot, req, t0_dev) deferred first tokens
    spec: bool              # MTP draft+verify round?
    t0: float               # plan-stage entry time (decode_wall_s)


@dataclasses.dataclass
class _PrefillTask:
    """Chunk cursor of one admitting slot (engine-side prefill state)."""
    req: Request
    tokens: jax.Array        # [1, prompt_len]
    cursor: int = 0
    # rolling per-layer post-ln1 tails of the last `warmup_windows` prompt
    # positions (accumulated across chunks so warmup depth never depends
    # on prompt_len % prefill_chunk)
    tails: Optional[list] = None


class ServeSession:
    """One long-lived ESS decode batch driven by the continuous-batching
    scheduler.

    * ``num_slots`` decode slots share one jit-shaped batch; more requests
      than slots stream through as slots free up.
    * Prefill is **chunked and interleaved**: each serve round runs one
      ``prefill_chunk``-token chunk for at most one admitting slot plus one
      decode step for all running slots.  Chunk latents scatter straight
      into the slot's mapped host pages (no max_seq-sized donor cache, no
      graft), so admitting a long prompt never stalls the decode batch —
      it costs one chunk per round.
    * With the paged host tier, admission is gated on **free host pages**
      (``pages = ceil((prompt + max_new) / page_rows)`` per request) and
      free Sparse-Memory-Pool entries; ``num_host_pages`` can be provisioned
      *below* ``num_slots × blocks_per_slot`` — the dense layout's pin — to
      exercise the gate.
    * A finished or preempted slot returns its pages to the allocator and
      gets a full per-slot cache reset (``reset_slot``: lens + pool maps),
      so a recycled slot can never take pool hits on the previous
      occupant's latents.  Decode steps gate inactive slots *in-step*
      (``slot_mask``), so a freed or mid-prefill slot can never scatter a
      phantom latent row or pollute its pool between admissions.
    * ``mtp_depth > 0`` runs each decode round as an **MTP speculative
      round** over the live batch: draft ``mtp_depth`` tokens per slot
      from the carried backbone hidden (``mtp_draft``), verify all drafts
      with one ``ess_decode`` call at ``Q = depth+1``, emit the accepted
      prefix + bonus token, and roll back lens/pools for rejected drafts
      (frozen slots gated — see ``speculative_step``).  Greedy output is
      bit-identical to the Q=1 baseline; sampling requests degrade to
      exact Q=1 emission inside the round.
    * ``tbo=True`` composes Two-Batch Overlap: every decode/verify step
      splits the batch into two half-batches (``split_caches``), steps
      them as independent programs so half-A's H2D pool fetches overlap
      half-B's compute, and reconciles the shared paged host tier by page
      ownership (``merge_caches``).
    * ``compiled=True`` (the default) runs every round as a **donated
      jitted StepProgram** (:mod:`repro.serving.step`) over the
      device-resident :class:`~repro.serving.state.EngineState`: token
      selection (greedy *and* per-slot temperature/top-k/top-p sampling)
      happens in-device and the host fetches exactly one packed
      ``(tokens [B,Q], n_emit [B])`` struct per decode round.  Host code
      keeps only scheduler bookkeeping, page allocation and stream
      emission.  ``compiled=False`` (the debugging path) executes the
      *same* round functions with the glue op-by-op but the same jitted
      floating-point units (model step, speculative core, samplers), so
      both modes emit bit-identical streams — see
      :mod:`repro.serving.step`.  ``do_warmup=True`` prefill chunks take
      the legacy eager path (the LRU-warmup replay is host-driven);
      decode rounds still compile.
    """

    def __init__(self, params, cfg: ArchConfig, *, num_slots: int,
                 max_seq: int, num_host_pages: Optional[int] = None,
                 host_byte_budget: Optional[int] = None,
                 prompt_fn: Optional[Callable[[Request], jax.Array]] = None,
                 do_warmup: bool = False, use_kernel: bool = False,
                 prefill_chunk: int = 64, mtp_depth: int = 0,
                 tbo: bool = False, compiled: bool = True,
                 overlap: bool = False,
                 prefetch_rows: Optional[int] = None):
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.do_warmup = do_warmup
        self.use_kernel = use_kernel
        self.compiled = compiled
        self.prefill_chunk = max(1, prefill_chunk)
        if mtp_depth > 0 and mtp_depth > cfg.mtp_depth:
            raise ValueError(f"mtp_depth {mtp_depth} > cfg.mtp_depth "
                             f"{cfg.mtp_depth} stacked draft modules")
        self.mtp_depth = max(0, mtp_depth)
        self.tbo = tbo and num_slots >= 2
        # async-offload pipeline: size the staging slab to the steady
        # -state miss envelope (the same max_miss_ratio * K bound the
        # lookup provisions) unless the caller pins it explicitly
        self.overlap = overlap
        if overlap:
            self.prefetch_rows = prefetch_rows if prefetch_rows is not None \
                else max(1, int(cfg.ess.max_miss_ratio
                                * min(cfg.dsa.index_topk, max_seq)))
        else:
            self.prefetch_rows = 0
        self.paged = LC.uses_paged_host(cfg)
        blocks_per_slot = LC.num_blocks(cfg, max_seq) if cfg.ess.enabled \
            else 0
        self.num_pages = 0
        self.allocator: Optional[LC.HostPageAllocator] = None
        # dtype-exact tier widths: admission reasons in BYTES, so a fixed
        # host budget admits ~2x the pages when the tier is quantized
        # (int8 payload + f16 scale vs bf16 rows)
        self.host_row_bytes = LC.host_row_bytes(cfg, cfg.param_dtype) \
            if cfg.ess.enabled else 0
        self.host_page_bytes = LC.host_page_bytes(cfg, cfg.param_dtype) \
            if cfg.ess.enabled else 0
        if self.paged:
            if host_byte_budget is not None:
                # byte-denominated provisioning: floor to whole pages of
                # the *storage* dtype.  num_host_pages, if also given, is
                # an additional cap.
                by_bytes = host_byte_budget // max(1, self.host_page_bytes)
                self.num_pages = by_bytes if num_host_pages is None \
                    else min(by_bytes, num_host_pages)
            else:
                self.num_pages = (num_host_pages
                                  if num_host_pages is not None
                                  else num_slots * blocks_per_slot)
            self.allocator = LC.HostPageAllocator(self.num_pages)
        caches = LC.init_ess_caches(
            cfg, num_slots, max_seq, cfg.param_dtype,
            num_pages=self.num_pages if self.paged else None,
            map_slots=not self.paged)
        # the device-resident round state: caches + tok/hidden carries +
        # per-slot sampling knobs + live/sampling masks.  The compiled
        # StepPrograms donate it every round; host code touches it only
        # at slot-lifecycle edges with .at[slot] updates.
        self.state = ES.init_engine_state(cfg, caches, num_slots,
                                          prefetch_rows=self.prefetch_rows)
        # the host half of the pipeline (slab arming + lifecycle-edge
        # cancellation + commit accounting); None when synchronous
        self.transfer: Optional[TR.TransferEngine] = None
        if self.prefetch_rows > 0:
            self.transfer = TR.TransferEngine(
                cfg.num_layers, num_slots, self.prefetch_rows,
                caches.host_latent.shape[-1], caches.host_latent.dtype,
                scale_dtype=None if caches.host_scales is None
                else caches.host_scales.dtype)
        self._programs = SP.get_programs(cfg, num_slots, max_seq,
                                         use_kernel, self.tbo,
                                         self.mtp_depth,
                                         self.prefetch_rows)
        self.pool_entries_per_slot = LC.pool_entries(cfg, max_seq)
        self.free_pool_entries = num_slots * self.pool_entries_per_slot
        self.sched = Scheduler(num_slots, max_seq,
                               admission_gate=self._admission_gate,
                               release_hook=self._release_slot,
                               reject_hook=self._reject)
        # per-request emitted token stream (prefill first-token + decode
        # emissions, truncated to max_new_tokens); reset on re-admission
        self.outputs: dict[int, list[int]] = {}
        self.report = ServeReport(num_pages=self.num_pages,
                                  host_bytes_per_row=self.host_row_bytes)
        # request-lifecycle event stream: every delivered token and every
        # terminal record (exactly one per rid) as TokenEvents.
        # `token_events` is the full log (latency accounting);
        # `_pending_events` buffers the current round for step_round()'s
        # return / the front-end's drain.
        self.token_events: list[TokenEvent] = []
        self._pending_events: list[TokenEvent] = []
        self._terminal: dict[int, str] = {}     # rid -> finish_reason
        self._last_done: list[Request] = []
        self._prompt_fn = prompt_fn or self._default_prompt
        # resources promised to earlier admissions of the same admit batch
        # (the scheduler consults the gate before the engine allocates)
        self._promised_pages = 0
        self._promised_slots = 0
        # chunked-prefill state machine: slot -> task, FIFO service order
        # by dict insertion (re-admissions re-insert at the back)
        self._prefill: dict[int, _PrefillTask] = {}
        # just-promoted slots whose on-device first tokens still await
        # delivery: [(slot, req, t0_dev)].  decode_round packs them into
        # the round's single device_get (one-fetch contract); the normal
        # step_round cadence holds at most one entry
        self._pending_first: list[tuple] = []
        # decode rounds each slot has run since its promotion — the
        # pipeline-fill window detector for ServeReport.fill_rounds
        self._rounds_since_promote: dict[int, int] = {}
        self._round = 0
        self._submit_round: dict[int, int] = {}
        self._submit_time: dict[int, float] = {}

    # -- device-state views (compat accessors over EngineState) --------------

    @property
    def caches(self) -> LC.ESSCaches:
        return self.state.caches

    @caches.setter
    def caches(self, value: LC.ESSCaches) -> None:
        self.state = self.state._replace(caches=value)

    @property
    def tok(self) -> jax.Array:
        """[B] next input token per slot (device-resident)."""
        return self.state.tok

    @property
    def hidden(self) -> jax.Array:
        """[B,d] post-final-norm hidden at each slot's last accepted
        position — the MTP draft seed, carried across rounds and across
        the prefill -> decode promotion (device-resident)."""
        return self.state.hidden

    # -- resource accounting -------------------------------------------------

    def _default_prompt(self, req: Request) -> jax.Array:
        return jax.random.randint(jax.random.key(1000 + req.rid),
                                  (1, req.prompt_len), 0,
                                  self.cfg.vocab_size)

    def pages_needed(self, req: Request) -> int:
        return LC.pages_for_len(self.cfg, req.prompt_len + req.max_new_tokens)

    def _admission_gate(self, req: Request) -> bool:
        # pool-entry gate: with today's per-slot dedicated pools this tracks
        # slot freeness exactly (the scheduler already enforces it); it is
        # the accounting hook that becomes load-bearing once the Sparse
        # Memory Pool is shared across slots
        need_entries = self.pool_entries_per_slot * (self._promised_slots + 1)
        if self.free_pool_entries < need_entries:
            return False
        need = self.pages_needed(req)
        if self.allocator is not None:
            # byte-denominated gate (dtype-aware): pages are the
            # allocation unit, but the resource being rationed is host
            # bytes — a quantized tier's smaller pages admit ~2x the
            # requests into the same byte budget
            need_bytes = need * self.host_page_bytes
            free_bytes = (self.allocator.free_pages
                          - self._promised_pages) * self.host_page_bytes
            if need_bytes > free_bytes:
                ev = (f"blocked rid={req.rid}: needs {need_bytes} host "
                      f"bytes ({need} pages), {free_bytes} free")
                if not self.report.events or self.report.events[-1] != ev:
                    self.report.events.append(ev)
                return False
        self._promised_pages += need
        self._promised_slots += 1
        return True

    def _release_slot(self, slot: int) -> None:
        # a mid-prefill preemption drops the chunk cursor: the attempt
        # re-prefills from scratch on its next admission
        self._prefill.pop(slot, None)
        if self.allocator is not None:
            self.allocator.release(slot)
            self.caches = LC.unmap_slot(self.caches, slot)
        self.caches = LC.reset_slot(self.caches, slot)
        self.state = ES.release_slot(self.state, slot)
        self._rounds_since_promote.pop(slot, None)
        self.free_pool_entries += self.pool_entries_per_slot

    def _sample_pages(self) -> None:
        if self.allocator is not None:
            used = self.num_pages - self.allocator.free_pages
            self.report.peak_pages_in_use = max(
                self.report.peak_pages_in_use, used)

    # -- event stream --------------------------------------------------------

    def _event(self, ev: TokenEvent) -> None:
        self._pending_events.append(ev)
        self.token_events.append(ev)

    def drain_events(self) -> list[TokenEvent]:
        """Hand the buffered TokenEvents to the front-end (also returned
        by :meth:`step_round`; this drains out-of-round events too —
        submit-time rejections, between-round aborts)."""
        evs, self._pending_events = self._pending_events, []
        return evs

    def _finalize(self, req: Request) -> None:
        """Emit the request's single terminal event.  Every request ends
        here exactly once, whatever the path (natural completion, stop
        token, abort, rejection, round-budget kill)."""
        reason = req.finish_reason or "length"
        assert req.rid not in self._terminal, \
            f"rid={req.rid} already terminal ({self._terminal[req.rid]})"
        self._terminal[req.rid] = reason
        self.report.finish_reasons[req.rid] = reason
        self._event(TokenEvent(rid=req.rid, token=None,
                               index=len(self.outputs.get(req.rid, [])),
                               finish_reason=reason,
                               t=time.perf_counter()))

    def _reject(self, req: Request) -> None:
        """Scheduler reject hook: an oversize request bounced at
        admission surfaces as a terminal ``rejected`` event + counter
        instead of silently vanishing."""
        self.report.rejected += 1
        self.report.events.append(
            f"rejected rid={req.rid}: prompt {req.prompt_len} + max_new "
            f"{req.max_new_tokens} > max_seq {self.sched.max_seq}")
        self._finalize(req)

    # -- request flow --------------------------------------------------------

    def submit(self, req: Request) -> None:
        # unconditional stamps: a missing rid must surface as a KeyError
        # at delivery, never as a silently ~0 TTFT (the old defaulted
        # lookup reported perf_counter() - perf_counter() for it)
        self._submit_round[req.rid] = self._round
        self._submit_time[req.rid] = time.perf_counter()
        # a request needing more pages than the whole pool can never be
        # admitted — reject up front instead of blocking the queue
        # forever (the scheduler itself only screens against max_seq)
        if self.allocator is not None \
                and self.pages_needed(req) > self.num_pages:
            req.finished = True
            req.finish_reason = "rejected"
            self.sched.finished.append(req)
            self.report.rejected += 1
            self.report.events.append(
                f"rejected rid={req.rid}: needs {self.pages_needed(req)} "
                f"pages, pool has {self.num_pages}")
            self._finalize(req)
            return
        self.sched.submit(req)

    def abort(self, rid: int, *, reason: str = "abort") -> bool:
        """Abort a queued or running request between rounds.  A running
        slot's host pages return to the allocator immediately and the
        slot gets the full reset (pool maps + lens + engine masks) via
        the scheduler's release hook — mid-prefill aborts also drop the
        chunk cursor; the stream closes with one terminal event."""
        req = self.sched.running.get(rid)
        if req is None:
            req = next((r for r in self.sched.queue if r.rid == rid), None)
        if req is None or req.finished:
            return False
        req.finish_reason = reason
        released = self.sched.abort(rid)
        assert released
        self.report.aborted += 1
        self.report.events.append(
            f"round {self._round}: rid={rid} aborted ({reason})")
        self._finalize(req)
        return True

    def preempt(self, slot: int) -> None:
        """Evict a running slot (node loss / rebalance); pages return and
        the slot's caches are fully reset via the scheduler's hook."""
        self.sched.preempt(slot)

    def admit(self) -> list[tuple[int, Request]]:
        """Admit queued requests into free slots: allocate + map host pages
        and enqueue the slot on the chunked-prefill state machine.  The
        prompt itself streams in ``prefill_chunk``-token chunks across
        subsequent :meth:`prefill_round` calls — admission never blocks the
        decode batch on a monolithic prefill."""
        self._promised_pages = 0
        self._promised_slots = 0
        admitted = self.sched.admit()
        for slot, req in admitted:
            if self.allocator is not None:
                pages = self.allocator.alloc(slot, self.pages_needed(req))
                self.caches = LC.map_slot(self.caches, slot, pages)
            self._sample_pages()
            self.free_pool_entries -= self.pool_entries_per_slot
            self._prefill[slot] = _PrefillTask(req, self._prompt_fn(req))
            # install the request's sampling knobs into the device state
            # (the slot itself stays frozen until the last prefill chunk)
            self.state = ES.admit_slot(self.state, slot, req)
            # a preempted re-admission regenerates its full stream
            self.outputs[req.rid] = []
            self.report.events.append(
                f"round {self._round}: rid={req.rid} -> slot {slot} "
                f"(prefill {req.prompt_len} toks, "
                f"preempted {req.preempted_count}x)")
        return admitted

    def prefill_round(self) -> bool:
        """Run one prefill chunk for the oldest admitting slot (if any).

        The chunk's latents and indexer keys scatter directly into the
        slot's mapped host pages.  Without warmup the chunk runs as a
        shape-bucketed StepProgram (ragged final chunks zero-padded to
        the bucket, masked via ``n_valid`` — no retrace, bit-identical
        valid rows) that also selects the first token in-device and
        promotes the slot inside the program; with ``do_warmup`` the
        legacy eager chunk collects the per-layer warmup tails and the
        LRU replay runs after the last chunk.

        **One-fetch contract**: a last chunk does *not* fetch its first
        token here.  The promotion bookkeeping is token-free, so the slot
        promotes immediately and the on-device ``t0`` is stashed; it
        rides this round's single packed ``device_get`` in
        :meth:`decode_round` (the promoted slot is active, so the decode
        program always runs).  Only the legacy ``do_warmup`` path — whose
        chunk is eager and host-driven anyway — still resolves ``t0``
        inline."""
        if not self._prefill:
            return False
        slot = next(iter(self._prefill))         # FIFO by insertion order
        task = self._prefill[slot]
        with TraceAnnotation("ess.prefill", rid=task.req.rid):
            n = task.req.prompt_len
            c0 = task.cursor
            ck = min(self.prefill_chunk, n - c0)
            last = c0 + ck >= n
            if self.do_warmup:
                t0 = self._prefill_chunk_warmup(slot, task, c0, ck, n, last)
                t0_dev = None
            else:
                C = SP.chunk_bucket(ck, self.prefill_chunk)
                toks = task.tokens[:, c0:c0 + ck]
                if C > ck:
                    toks = jnp.pad(toks, ((0, 0), (0, C - ck)))
                fn = self._programs.prefill(C, last, self.compiled)
                self.state, t0_dev = fn(self.params, self.state, toks,
                                        jnp.asarray(slot, jnp.int32),
                                        jnp.asarray(ck, jnp.int32))
            task.cursor += ck
            self.report.prefill_chunks += 1
            self.report.prefill_tokens += ck
            self.report.events.append(
                f"round {self._round}: rid={task.req.rid} prefill chunk "
                f"[{c0}:{c0 + ck})/{n} (slot {slot})")
            if last:
                if self.do_warmup:
                    self._finish_prefill(slot, task, t0)
                else:
                    req = task.req
                    self.sched.promote(slot)
                    self._rounds_since_promote[slot] = 0
                    del self._prefill[slot]
                    self._pending_first.append((slot, req, t0_dev))
        return True

    def _prefill_chunk_warmup(self, slot: int, task: _PrefillTask, c0: int,
                              ck: int, n: int, last: bool) -> Optional[int]:
        """Legacy eager prefill chunk for ``do_warmup`` sessions: ragged
        chunk shapes, per-layer tail collection across chunks, LRU-warmup
        replay after the last chunk, host-side first-token draw."""
        W = max(0, min(self.cfg.ess.warmup_windows, n - 1))
        toks = task.tokens[:, c0:c0 + ck]
        pos = jnp.arange(c0, c0 + ck, dtype=jnp.int32)[None]
        lg, self.caches, tails, hid_last = ess_prefill_chunk(
            self.params, self.cfg, toks, pos, self.caches, slot=slot,
            want_logits=last, collect_tail=min(W, ck),
            use_kernel=self.use_kernel, n_valid=None)
        if W > 0:
            if task.tails is None:
                task.tails = list(tails)
            else:
                task.tails = [jnp.concatenate([a, b], axis=1)[:, -W:]
                              for a, b in zip(task.tails, tails)]
        if not last:
            return None
        if W > 0:
            self._warmup_slot(slot, tuple(task.tails), n)
        req = task.req
        # legacy eager warmup path: syncs per chunk by design (the
        # compiled path defers t0 into decode_round's packed fetch)
        if req.sampling:
            t0 = int(self._draw(req, lg[0, -1], 0))    # esslint: disable=ESS002
        else:
            t0 = int(greedy(lg[:, -1])[0])             # esslint: disable=ESS002
        self.state = ES.promote_slot(self.state, slot, t0, hid_last[0])
        return t0

    def _deliver_first_token(self, slot: int, req: Request, t0: int,
                             now: Optional[float] = None) -> Optional[str]:
        """Deliver a freshly promoted slot's first token (stream + event
        + TTFT stamps).  ``now`` is the round's delivery time — the
        instant the packed fetch landed on the host — so latency stamps
        measure when the token became *available*, not when the commit
        stage's bookkeeping got around to it.  Returns the terminal kind
        if the request is already done at its first token — ``"stop"``
        (t0 is an EOS/stop token) or ``"length"`` (``max_new_tokens ==
        1`` spent the whole budget) — else ``None``."""
        if now is None:
            now = time.perf_counter()
        self.outputs[req.rid] = [t0]
        self._event(TokenEvent(rid=req.rid, token=t0, index=0, t=now))
        rid = req.rid
        ttft = self._round - self._submit_round[rid]
        # a preempted request's first token was already delivered by its
        # first attempt: keep that TTFT
        self.report.ttft_rounds.setdefault(rid, ttft)
        self.report.ttft_s.setdefault(rid, now - self._submit_time[rid])
        self.report.events.append(
            f"round {self._round}: rid={rid} first token ready "
            f"(ttft {ttft} rounds)")
        if t0 in req.stop_set:
            req.finish_reason = "stop"
            return "stop"
        if self.sched.budget_left(slot) == 0:
            return "length"
        return None

    def _finish_prefill(self, slot: int, task: _PrefillTask,
                        t0: int) -> None:
        """Legacy (``do_warmup``) promotion bookkeeping after the last
        prefill chunk: deliver the host-resolved first token and promote
        the slot into the decode batch.  A ``max_new_tokens == 1``
        request's budget is spent by the first token — it finishes right
        here, before any decode round; so does a request whose first
        token is one of its EOS/stop tokens.  (The compiled path defers
        delivery to :meth:`decode_round`'s packed fetch instead.)"""
        req = task.req
        self.sched.promote(slot)
        self._rounds_since_promote[slot] = 0
        del self._prefill[slot]
        done = self._deliver_first_token(slot, req, t0)
        if done == "stop":
            self._handle_done([self.sched.finish(slot)])
        elif done == "length":
            self._handle_done(self.sched.record_tokens({slot: 0}))

    def _warmup_slot(self, slot: int, tails: tuple, prompt_len: int) -> None:
        """LRU-Warmup replay for one freshly prefilled slot (paper §3.2):
        the Top-K sets of the last W prefill windows are inserted into a
        fresh batch-1 pool from the slot's mapped pages, then grafted into
        the shared Sparse Memory Pool with clock-clamped stamps."""
        lens1 = jnp.full((1,), prompt_len, jnp.int32)
        pools = []
        for layer, x_tail in enumerate(tails):
            lp, _ = _layer_params(self.params, self.cfg, layer)
            full = self.caches.pools[layer]
            one = LP.init_pool(1, full.data.shape[1],
                               self.caches.ikeys[layer].shape[1],
                               full.data.shape[2], full.data.dtype)
            ik_slot = jax.lax.slice_in_dim(self.caches.ikeys[layer], slot,
                                           slot + 1, axis=0)
            one = warmup.lru_warmup(
                one, self.caches.host_latent, x_tail, lp["indexer"], ik_slot,
                lens1, self.cfg, slot_mask=None, layer=layer,
                batch_offset=slot, block_table=self.caches.block_tables,
                host_scales=self.caches.host_scales)
            pools.append(LC.graft_pool_into(full, one, slot))
        self.caches = self.caches._replace(pools=tuple(pools))

    # -- decode stepping -----------------------------------------------------

    def _slot_req(self, slot: int) -> Request:
        return self.sched.running[self.sched.slots[slot].rid]

    def _draw(self, req: Request, logits: jax.Array, index: int):
        """Sample one token for a sampling request.  ``index`` is the
        chain position (0 = prefill first token, ``generated + 1`` in
        decode rounds) — the single key-derivation point that keeps
        sampled streams identical across Q=1 and speculative modes."""
        return sample(request_key(req.sample_seed, index), logits,
                      req.temperature, req.top_k, req.top_p)

    def _emit(self, slot: int, req: Request, tokens: list[int],
              now: Optional[float] = None) -> tuple[int, bool]:
        """Deliver a round's emitted tokens for one slot: extend the
        request's output stream (as TokenEvents too) and return
        ``(generated-budget charge, stop-token hit)``.
        Charge == delivery, always: both are clamped by the *same*
        ``remaining`` headroom (budget and max_seq), so the scheduler
        never records a token that was not appended to the stream —
        ``len(outputs[rid]) == generated + 1`` holds at finish (the old
        code charged ``min(len(tokens), remaining)`` while delivering
        under an additional ``max_new - len(out)`` clamp, so a verify
        round at the budget edge recorded ghost tokens).

        EOS/stop-token termination cuts *within* the round: the stream
        ends exactly at the stop position (the stop token is the last
        delivery) and the caller rolls back the over-accepted suffix an
        MTP verify round may have appended past it.

        ``now`` (the round's post-fetch delivery instant) stamps the
        TokenEvents, keeping ITL a delivery-latency measure rather than
        a commit-latency one."""
        out = self.outputs.setdefault(req.rid, [])
        delivered = tokens[:max(0, self.sched.remaining(slot))]
        stops = req.stop_set
        stopped = False
        if stops:
            for j, t in enumerate(delivered):
                if t in stops:
                    delivered = delivered[:j + 1]
                    stopped = True
                    break
        if now is None:
            now = time.perf_counter()
        for t in delivered:
            self._event(TokenEvent(rid=req.rid, token=t, index=len(out),
                                   t=now))
            out.append(t)
        if stopped:
            req.finish_reason = "stop"
        return len(delivered), stopped

    def _truncate_slot_tail(self, slot: int, n_drop: int) -> None:
        """Roll back the last ``n_drop`` appended positions of one slot
        (stop-token termination inside a speculative round): ``lens``
        shrink and pool entries beyond are invalidated — exactly the MTP
        rejection rollback, so the slot's lens/pool state matches a run
        that never drafted past the stop position.  (Indexer-cache and
        host rows beyond ``lens`` are dead by construction and reset
        with the slot.)"""
        if n_drop <= 0:
            return
        caches = self.caches
        new_lens = caches.lens.at[slot].add(jnp.int32(-n_drop))
        pools = tuple(LP.invalidate_beyond(p, new_lens)
                      for p in caches.pools)
        self.caches = caches._replace(lens=new_lens, pools=pools)
        if self.transfer is not None:
            # cancel staged transfers landing beyond the rollback point —
            # their host rows are about to be overwritten by the re-append
            # and would otherwise serve dead-draft latents next round.
            # new_lens[slot] stays a traced device scalar: an int() here
            # would be a second host sync inside the round (ESS102).
            self.state = self.transfer.truncate_slot(self.state, slot,
                                                     new_lens[slot])

    def _plan_round(self) -> Optional["_RoundPlan"]:
        """**Plan stage** of the round pipeline: decide what this round
        runs before any device work — sample page pressure, collect the
        just-promoted slots whose first tokens are still on device, and
        pick the round kind.  Returns ``None`` when no slot is active
        (a pipeline fill/drain round: nothing to compute, and the round
        is *not* counted toward the decode cadence).  The speculative
        plan half — which rows to stage for round N+1 — is traced inside
        the round program itself (``ess_decode``'s plan stage), where the
        indexer scores live."""
        self._sample_pages()
        pending, self._pending_first = self._pending_first, []
        # drop stale entries (slot preempted/aborted before its first
        # token was fetched — the re-admission regenerates the stream)
        pending = [(s, r, t) for s, r, t in pending
                   if self.sched.slots[s].active
                   and self.sched.slots[s].rid == r.rid]
        active = self.sched.active_slots()
        if not active:
            assert not pending       # a promoted slot is always active
            return None
        return _RoundPlan(active=active, pending=pending,
                          spec=self.mtp_depth > 0,
                          t0=time.perf_counter())

    def _compute_round(self, plan: "_RoundPlan") -> ES.RoundOut:
        """**Compute stage**: launch the round's donated StepProgram over
        the device state and return its packed :class:`RoundOut` handle —
        still on device; nothing here blocks the host.  With overlap on,
        the program consumes the slab staged by round N-1 and leaves
        round N+1's staging transfer in flight inside the same program."""
        fn = self._programs.spec(self.compiled) if plan.spec \
            else self._programs.decode(self.compiled)
        self.state, out = fn(self.params, self.state)
        return out

    def _commit_round(self, plan: "_RoundPlan",
                      out: ES.RoundOut) -> list[Request]:
        """**Commit stage**: the round's single packed fetch (one-fetch
        contract — decode emissions, the just-promoted slots' deferred
        first tokens, and the prefetch counters all ride one
        ``device_get``), then scheduler bookkeeping + stream emission.
        Every TokenEvent is stamped with the post-fetch *delivery*
        instant, not the time this bookkeeping finishes."""
        pf = () if out.pf_hits is None else \
            (out.pf_hits, out.pf_misses, out.pf_wasted)
        h2d = () if out.h2d_rows is None else (out.h2d_rows,)
        t0_devs = [t for _, _, t in plan.pending]
        with TraceAnnotation("ess.fetch"):
            toks, n_emit, t0s, pf_host, h2d_host = jax.device_get(
                (out.tokens, out.n_emit, t0_devs, pf, h2d))
        t_deliver = time.perf_counter()
        with TraceAnnotation("ess.commit"):
            return self._commit_tokens(plan, toks, n_emit, t0s, pf_host,
                                       h2d_host, t_deliver)

    def _commit_tokens(self, plan: "_RoundPlan", toks, n_emit, t0s,
                       pf_host, h2d_host, t_deliver: float
                       ) -> list[Request]:
        """Commit-stage bookkeeping over the fetched host values: counters,
        first-token and token delivery, stop-token rollback, scheduler
        accounting."""
        active, pending, spec = plan.active, plan.pending, plan.spec
        if pf_host:
            self.transfer.commit(self.report, pf_host[0].sum(),
                                 pf_host[1].sum(), pf_host[2].sum())
        if h2d_host:
            self.report.h2d_rows += int(h2d_host[0])
        # decode-round D2H writeback: every live slot appends Q latent
        # rows per layer (compressed width on a quantized tier)
        q_round = (self.mtp_depth + 1) if spec else 1
        self.report.d2h_rows += len(active) * q_round * self.cfg.num_layers
        slot_tokens = {}
        stop_slots = []
        first_done = {}
        for (s0, r0, _), t0 in zip(pending, t0s):
            fd = self._deliver_first_token(s0, r0, int(t0), now=t_deliver)
            if fd is not None:
                first_done[s0] = fd
        for i in active:
            req = self._slot_req(i)
            if i in first_done:
                # the request ended at its very first token (stop token
                # or max_new_tokens == 1); the decode step the program
                # already took for the slot is discarded wholesale when
                # the slot releases (full reset: lens, pool maps, pages)
                slot_tokens[i] = 0
                if first_done[i] == "stop":
                    stop_slots.append(i)
                continue
            n = int(n_emit[i])
            charged, stopped = self._emit(i, req,
                                          [int(t) for t in toks[i, :n]],
                                          now=t_deliver)
            slot_tokens[i] = charged
            if stopped:
                # the verify round drafted past the stop: drop the
                # over-accepted suffix from the slot's lens + pools
                # (staged transfers beyond the cut are cancelled too)
                self._truncate_slot_tail(i, n - charged)
                stop_slots.append(i)
            if spec and not req.sampling:
                self.report.drafted_tokens += self.mtp_depth
                self.report.accepted_tokens += n - 1
        done = self.sched.record_tokens(slot_tokens)
        for i in stop_slots:
            if self.sched.slots[i].active:   # not already budget-finished
                done.append(self.sched.finish(i))
        # a round is *fill* while any stepping slot is still inside its
        # pipeline-fill window; fill rounds count toward `rounds` but not
        # toward the decode cadence (numerator nor denominator) — see
        # ServeReport.fill_rounds.  The window is a function of the
        # admission schedule alone, so sync and overlapped runs classify
        # identical rounds.
        fill = any(self._rounds_since_promote.get(i, PIPELINE_FILL_ROUNDS)
                   < PIPELINE_FILL_ROUNDS for i in active)
        for i in active:
            if self._rounds_since_promote.get(i, 99) < PIPELINE_FILL_ROUNDS:
                self._rounds_since_promote[i] += 1
        self.report.rounds += 1
        if spec:
            self.report.spec_rounds += 1
        self.report.decode_tokens += sum(slot_tokens.values())
        if fill:
            self.report.fill_rounds += 1
        else:
            self.report.decode_wall_s += time.perf_counter() - plan.t0
        return done

    def decode_round(self) -> list[Request]:
        """One decode round over the running slots; returns newly
        finished.

        The round is an explicit three-stage pipeline —
        :meth:`_plan_round` → :meth:`_compute_round` →
        :meth:`_commit_round`.  The whole compute — model step (Q=1, or
        the fused MTP draft+verify when ``mtp_depth > 0``, TBO halves
        included), greedy/sampled token selection, ``tok``/``hidden``
        carries, and (with ``overlap``) the staged-slab consumption +
        next round's prefetch staging — runs as one StepProgram over the
        donated device state; inactive and mid-prefill slots are masked
        *inside* the step (``slot_mask``).  The host fetches exactly one
        packed struct per round in the commit stage."""
        with TraceAnnotation("ess.plan"):
            plan = self._plan_round()
        if plan is None:
            return []
        with TraceAnnotation("ess.launch"):
            out = self._compute_round(plan)
        return self._commit_round(plan, out)

    def _handle_done(self, done: list[Request]) -> None:
        for req in done:
            out = self.outputs.get(req.rid, [])
            assert len(out) == req.generated + 1, \
                (f"rid={req.rid}: delivered {len(out)} != "
                 f"generated {req.generated} + first token")
            self._finalize(req)
            self.report.events.append(
                f"round {self._round}: rid={req.rid} finished "
                f"({len(out)} tokens, {req.finish_reason})")

    def step_round(self) -> list[TokenEvent]:
        """The re-entrant engine core: one serve round — admissions, then
        one prefill chunk for at most one admitting slot, then one decode
        step for all running slots — returning the round's TokenEvents
        (token deliveries + terminal records).  The front-end
        (:class:`repro.serving.api.EssEngine`) drives this directly;
        ``submit`` and ``abort`` may be called between any two rounds.
        Wall time accumulates per round, so throughput metrics hold for
        any driver (``run``, ``generate``, manual ``step`` loops)."""
        t0 = time.perf_counter()
        with StepTraceAnnotation("ess.round", step_num=self._round):
            with TraceAnnotation("ess.admit"):
                self.admit()
            self.prefill_round()
            done = self.decode_round()
            with TraceAnnotation("ess.finish"):
                self._handle_done(done)
        self._round += 1
        self._last_done = done
        self.report.wall_s += time.perf_counter() - t0
        return self.drain_events()

    def step(self) -> list[Request]:
        """Compat wrapper over :meth:`step_round` returning the round's
        newly finished requests (events stay buffered for drain)."""
        evs = self.step_round()
        self._pending_events = evs + self._pending_events
        return self._last_done

    def _terminate_remaining(self, reason: str) -> None:
        """Terminal records for every still-unfinished request (round
        budget exhausted): running slots release their pages, queued
        requests drop, each rid gets exactly one ``reason`` event."""
        for rid in [r.rid for r in self.sched.queue] + \
                list(self.sched.running):
            self.abort(rid, reason=reason)

    def run(self, requests=None, *, max_rounds: int = 200,
            on_round: Optional[Callable[["ServeSession", int], None]] = None
            ) -> ServeReport:
        """Compat shim: drive :meth:`step_round` until every submitted
        request reaches a terminal event (streams are bit-identical to
        the front-end's ``generate``).  Requests still unfinished after
        ``max_rounds`` rounds are terminated with
        ``finish_reason="budget"`` — nothing is ever stranded without a
        terminal record."""
        for req in (requests or []):
            self.submit(req)
        budget = max_rounds            # rounds granted to THIS run() call
        while self.sched.running or self.sched.queue:
            self.step_round()          # accumulates report.wall_s
            if on_round is not None:
                # the serve round just executed (aligned with event labels)
                on_round(self, self._round - 1)
            budget -= 1
            if budget <= 0:
                self.report.events.append("max_rounds reached")
                self._terminate_remaining("budget")
                break
        self.report.finished_rids = [r.rid for r in self.sched.finished]
        self.report.admissions_blocked = self.sched.blocked_admissions
        # lifecycle contract: every submitted rid ended with exactly one
        # terminal event (single-emission is enforced in _finalize)
        missing = [rid for rid in self._submit_round
                   if rid not in self._terminal]
        assert not missing, f"no terminal event for rids {missing}"
        return self.report
