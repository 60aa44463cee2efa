"""LRU-Warmup (paper §3.2): preheat the Sparse Memory Pool from the Top-2K
index sets of the last ``W`` prefill windows, inserted oldest-to-newest so
the LRU ordering matches early-decode access patterns (kills the initial
miss spike of Figure 4)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import lru_pool as LP
from repro.core import offload
from repro.models import mla as M


def lru_warmup(pool: LP.PoolState, host_latent: jax.Array,
               x_tail: jax.Array, idx_p: dict, idx_keys: jax.Array,
               lens: jax.Array, cfg: ArchConfig, *,
               slot_mask: jax.Array | None, layer: int = 0,
               batch_offset: int = 0,
               block_table: jax.Array | None = None,
               host_scales: jax.Array | None = None) -> LP.PoolState:
    """Seed the pool.

    x_tail [B, W, d]: post-ln1 hidden states of the last W prefill tokens
    (the "windows"); idx_keys [B, S, Di] full indexer cache; lens [B].
    Sequentially (scan) inserts each window's Top-K set with full LRU
    semantics, so stamps increase window by window.

    ``slot_mask`` is required keyword-only (ESS001): a ``[B]`` bool mask
    freezes masked rows' pool state through the whole warmup scan;
    ``None`` = every row live (e.g. the per-slot replay at admission).

    ``layer`` / ``batch_offset`` / ``block_table`` route the miss fetches
    through a stacked and/or paged host tier (the serve loop replays warmup
    per admitted slot against the slot's mapped pages).  ``host_scales``
    is the quantized tier's per-row scale plane (None = raw bf16): misses
    dequantize at miss width on the way into the pool, which stays bf16.
    """
    B, W, _ = x_tail.shape
    S = idx_keys.shape[1]
    K = min(cfg.dsa.index_topk, S)

    iq = M.indexer_query(idx_p, x_tail)                  # queries for W windows
    sc = M.indexer_scores(iq, idx_keys)                  # [B,W,S]
    ids_w = M.topk_ids(sc, K, jnp.arange(S) < lens[:, None, None])  # [B,W,K]
    valid_w = ids_w < lens[:, None, None]                # prefix mask

    def body(p, wi):
        ids, vw = wi                                     # [B,K]
        p, lk, _ = LP.lookup(p, ids, vw, K,              # envelope = K (exact)
                             slot_mask=slot_mask,
                             dedup=False)                # per-window top-k
        rows = offload.gather_tier_rows(host_latent, host_scales,
                                        lk.miss_ids, layer=layer,
                                        batch_offset=batch_offset,
                                        block_table=block_table)
        p = LP.admit(p, lk.miss_ids, rows, slot_mask=slot_mask)
        p = LP.tick(p)
        return p, None

    pool, _ = jax.lax.scan(body, pool,
                           (ids_w.transpose(1, 0, 2), valid_w.transpose(1, 0, 2)))
    return pool
