"""Multi-head Latent Attention (DeepSeek-V3) + DSA lightning indexer
(DeepSeek-V3.2-Exp).

Cache layout (per layer): one **latent entry** per token =
``concat(rmsnorm(c_kv) [kv_lora_rank], rope(k_pe) [qk_rope_head_dim])``
— 576 dims for the 671B config.  Decode uses the *absorbed* (FlashMLA)
formulation: attention becomes MQA of per-head 576-dim queries against the
shared latent cache, which is exactly the object ESS offloads.

The DSA indexer keeps its own per-token key (``index_dim`` dims).  It is
**never offloaded** (paper §3: full computation each step, 16.8 % of bytes).

Decode entry points are split so that ``repro.core.overlap`` can run Attn0
(pool hits) concurrently with the host fetch and merge Attn1 (misses)
exactly — see ``partial_sparse_attend`` / ``merge_partials``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.params import ParamDef

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def mla_def(cfg: ArchConfig) -> dict:
    m = cfg.mla
    dt = cfg.param_dtype
    d, H = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "w_dq": ParamDef((d, m.q_lora_rank), dt, "normal", axes=("embed", "lora")),
        "q_norm": ParamDef((m.q_lora_rank,), dt, "zeros", axes=("lora",)),
        "w_uq": ParamDef((m.q_lora_rank, H, qk), dt, "normal",
                         axes=("lora", "heads", None)),
        "w_dkv": ParamDef((d, m.kv_lora_rank), dt, "normal", axes=("embed", "lora")),
        "kv_norm": ParamDef((m.kv_lora_rank,), dt, "zeros", axes=("lora",)),
        "w_kr": ParamDef((d, m.qk_rope_head_dim), dt, "normal", axes=("embed", None)),
        "w_uk": ParamDef((m.kv_lora_rank, H, m.qk_nope_head_dim), dt, "normal",
                         axes=("lora", "heads", None)),
        "w_uv": ParamDef((m.kv_lora_rank, H, m.v_head_dim), dt, "normal",
                         axes=("lora", "heads", None)),
        "wo": ParamDef((H, m.v_head_dim, d), dt, "normal",
                       axes=("heads", None, "embed")),
    }
    return p


def indexer_def(cfg: ArchConfig) -> dict:
    i = cfg.dsa
    dt = cfg.param_dtype
    d = cfg.d_model
    return {
        "w_iq": ParamDef((d, i.index_heads, i.index_dim), dt, "normal",
                         axes=("embed", "idx", None)),
        "w_ik": ParamDef((d, i.index_dim), dt, "normal", axes=("embed", None)),
        "w_iw": ParamDef((d, i.index_heads), dt, "normal", axes=("embed", "idx"),
                         scale=0.02),
    }


def mla_scale(cfg: ArchConfig) -> float:
    m = cfg.mla
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5


# ---------------------------------------------------------------------------
# Latent construction (prefill / train / per-step append)
# ---------------------------------------------------------------------------

def latent_entries(p: dict, cfg: ArchConfig, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """x [B,S,d] -> latent cache entries [B,S,latent_dim] (rope baked in)."""
    m = cfg.mla
    c_kv = L.rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)
    k_pe = (x @ p["w_kr"])[:, :, None, :]              # [B,S,1,rope]
    cos, sin = L.rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
    k_pe = L.apply_rope(k_pe, cos[:, :, None, :], sin[:, :, None, :])[:, :, 0, :]
    return jnp.concatenate([c_kv, k_pe.astype(c_kv.dtype)], axis=-1)


def absorbed_query(p: dict, cfg: ArchConfig, x: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """x [B,Q,d] -> MQA query over latent space [B,Q,H,latent_dim]."""
    m = cfg.mla
    cq = L.rmsnorm(p["q_norm"], x @ p["w_dq"], cfg.norm_eps)
    q = jnp.einsum("bql,lhk->bqhk", cq, p["w_uq"])      # [B,Q,H,nope+rope]
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    cos, sin = L.rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_pe = L.apply_rope(q_pe, cos[:, :, None, :], sin[:, :, None, :])
    # absorb W_uk:  q_lat = q_nope @ W_uk^T  (per head)
    q_lat = jnp.einsum("bqhk,lhk->bqhl", q_nope, p["w_uk"])
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def output_proj(p: dict, cfg: ArchConfig, o_lat: jax.Array) -> jax.Array:
    """o_lat [B,Q,H,kv_lora_rank] -> [B,Q,d] (absorbed W_uv then W_o)."""
    o = jnp.einsum("bqhl,lhv->bqhv", o_lat, p["w_uv"])
    return jnp.einsum("bqhv,hvd->bqd", o, p["wo"])


# ---------------------------------------------------------------------------
# Indexer (DSA)
# ---------------------------------------------------------------------------

def indexer_keys(pi: dict, x: jax.Array) -> jax.Array:
    """Per-token indexer key [B,S,index_dim] — the Indexer-Cache entry."""
    return x @ pi["w_ik"]


class IndexerQuery(NamedTuple):
    q: jax.Array       # [B,Q,Hi,Di]
    w: jax.Array       # [B,Q,Hi]


def indexer_query(pi: dict, x: jax.Array) -> IndexerQuery:
    return IndexerQuery(jnp.einsum("bqd,dhk->bqhk", x, pi["w_iq"]),
                        jnp.einsum("bqd,dh->bqh", x, pi["w_iw"]))


def indexer_scores(iq: IndexerQuery, keys: jax.Array) -> jax.Array:
    """score[b,q,s] = sum_h w[b,q,h] * relu(q[b,q,h] . k[b,s])  (fp32)."""
    dots = jnp.einsum("bqhk,bsk->bqhs", iq.q.astype(jnp.float32),
                      keys.astype(jnp.float32))
    return jnp.einsum("bqh,bqhs->bqs", iq.w.astype(jnp.float32),
                      jax.nn.relu(dots))


def topk_ids(scores: jax.Array, k: int, valid_mask: jax.Array | None = None
             ) -> jax.Array:
    """Top-k cache indices per query row. scores [..., S] -> ids [..., k].

    Bit-identical to ``lax.top_k(where(valid_mask, scores, NEG_INF), k)[1]``
    — the same ids in the same order (score descending, the lower
    position first among equal scores, -0.0 below +0.0) — without sorting
    the S scores, which on the TPU is a full sort of every row: the k-th
    largest key is found by counting passes, the k kept positions are
    compacted in position order, and only those k are sorted."""
    if valid_mask is not None:
        scores = jnp.where(valid_mask, scores, NEG_INF)
    *lead, S = scores.shape
    u = _order_key(scores.reshape(-1, S).astype(jnp.float32))   # [R,S]
    ids, keys = _compact(_keep_mask(u, k), _in_blocks(u), k)
    _, ids = jax.lax.sort((~keys, ids), num_keys=2)
    return ids.reshape(*lead, k)


_BLOCK = 128        # positions per block of the running counts


def _order_key(x: jax.Array) -> jax.Array:
    """uint32 image of f32 ``x`` in ``lax.top_k``'s order (IEEE total
    order: -0.0 below +0.0): negatives bit-inverted, the sign bit set on
    the rest."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where((b >> 31) == 1, ~b, b | jnp.uint32(1 << 31))


def _in_blocks(x: jax.Array) -> jax.Array:
    """[R,S] -> [R,blocks,BLOCK], padded with zeros (False)."""
    R, S = x.shape
    nb = -(-S // _BLOCK)
    return jnp.pad(x, ((0, 0), (0, nb * _BLOCK - S))).reshape(R, nb, _BLOCK)


def _block_count(mask: jax.Array) -> jax.Array:
    """Inclusive running count of ``mask`` [R,blocks,BLOCK] within each
    block: a matmul with a triangle of ones (0/1 operands, sums of at most
    BLOCK: exact), where a cumsum along the whole row is slow on the
    TPU."""
    e = jnp.arange(_BLOCK)
    tri = (e[:, None] <= e[None, :]).astype(jnp.bfloat16)
    return jnp.einsum("rbi,ij->rbj", mask.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def _keep_mask(u: jax.Array, k: int) -> jax.Array:
    """[R,blocks,BLOCK] bool: exactly k keys of each row of ``u`` [R,S] —
    every key above the row's k-th largest, then the first of those equal
    to it by position.  The k-th largest is settled one bit a counting
    pass, from the top bit down."""
    def bit(i, thr):
        cand = thr | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (u >= cand[:, None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))
    gt = u > thr[:, None]
    eq = _in_blocks(u == thr[:, None])
    run = _block_count(eq)
    cnt = run[..., -1]
    run = run + (jnp.cumsum(cnt, -1) - cnt)[..., None]     # along the row
    n_tie = k - gt.sum(-1, dtype=jnp.int32)
    return _in_blocks(gt) | (eq & (run <= n_tie[:, None, None]))


def _compact(keep: jax.Array, u: jax.Array, k: int
             ) -> tuple[jax.Array, jax.Array]:
    """The k kept positions of each row of ``keep`` [R,blocks,BLOCK], in
    position order, with their keys from ``u`` (same shape):
    ``(ids [R,k] int32, keys [R,k] uint32)``.

    A gather on the TPU goes element by element, so this one is a matmul:
    kept j lies in the block whose kept count first passes j, and a
    one-hot [k, blocks] matrix brings that block's running kept count and
    the four bytes of its keys (integers below 256, exact in bf16) to row
    j, where the running count locates j within the block."""
    R, nb, _ = keep.shape
    run = _block_count(keep)
    cnt = run[..., -1]
    end = jnp.cumsum(cnt, -1)[:, None, :]                   # [R,1,nb]
    start = end - cnt[:, None, :]
    j = jnp.arange(k, dtype=jnp.int32)[None, :, None]
    hot = (start <= j) & (j < end)                          # [R,k,nb]
    blk = jnp.where(hot, jnp.arange(nb, dtype=jnp.int32), 0).sum(-1)
    rank = j[..., 0] - jnp.where(hot, start, 0).sum(-1)     # within blk
    planes = [run] + [((u >> (8 * i)) & 255).astype(jnp.int32)
                      for i in range(4)]
    g = jnp.einsum("rkb,rbe->rke", hot.astype(jnp.bfloat16),
                   jnp.concatenate(planes, -1).astype(jnp.bfloat16),
                   preferred_element_type=jnp.bfloat16)     # [R,k,5*BLOCK]
    pos = (g[..., :_BLOCK] <= rank[..., None].astype(g.dtype)).sum(
        -1, dtype=jnp.int32)
    at = jnp.arange(_BLOCK) == pos[..., None]
    keys = jnp.zeros((R, k), jnp.uint32)
    for i in range(4):
        byte = jnp.where(at, g[..., (i + 1) * _BLOCK:(i + 2) * _BLOCK], 0)
        keys = keys | (byte.sum(-1, dtype=jnp.float32).astype(jnp.uint32)
                       << (8 * i))
    return blk * _BLOCK + pos, keys


# ---------------------------------------------------------------------------
# Sparse attention over gathered latents (decode) — partials + exact merge
# ---------------------------------------------------------------------------

class Partial(NamedTuple):
    """Un-normalized attention partial (flash-decoding statistics)."""
    o: jax.Array       # [B,Q,H,latent_rank]  sum_j exp(s_j - m) * v_j
    m: jax.Array       # [B,Q,H]              running max
    l: jax.Array       # [B,Q,H]              sum_j exp(s_j - m)


def partial_sparse_attend(q_comb: jax.Array, latents: jax.Array,
                          valid: jax.Array, cfg: ArchConfig) -> Partial:
    """Attend q [B,Q,H,D] to gathered latents [B,K,D] with validity mask.

    Returns unnormalized partials so hit/miss halves merge exactly.
    This is the pure-jnp oracle for ``kernels/sparse_mla``.
    """
    rank = cfg.mla.kv_lora_rank
    s = jnp.einsum("bqhd,bkd->bqhk", q_comb, latents,
                   preferred_element_type=jnp.float32) * mla_scale(cfg)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    o = jnp.einsum("bqhk,bkv->bqhv", p.astype(latents.dtype),
                   latents[..., :rank], preferred_element_type=jnp.float32)
    l = p.sum(axis=-1)
    return Partial(o, m, l)


def merge_partials(a: Partial, b: Partial) -> Partial:
    m = jnp.maximum(a.m, b.m)
    ca = jnp.exp(a.m - m)
    cb = jnp.exp(b.m - m)
    return Partial(a.o * ca[..., None] + b.o * cb[..., None],
                   m, a.l * ca + b.l * cb)


def finalize_partial(pt: Partial, dtype=jnp.bfloat16) -> jax.Array:
    return (pt.o / jnp.maximum(pt.l, 1e-30)[..., None]).astype(dtype)


def sparse_mla_decode(p: dict, pi: dict, cfg: ArchConfig, x: jax.Array,
                      positions: jax.Array, latent_cache: jax.Array,
                      idx_keys: jax.Array, cache_len: jax.Array,
                      use_kernel: bool = False) -> tuple[jax.Array, jax.Array]:
    """Monolithic (non-ESS) DSA decode reference.

    x [B,Q,d]; latent_cache [B,S,D]; idx_keys [B,S,Di]; cache_len [B].
    Returns (out [B,Q,d], topk ids [B,Q,K]).  ESS replaces the gather with
    the pool/host split (see repro.core.overlap) but computes the same math.
    """
    S = latent_cache.shape[1]
    valid = jnp.arange(S)[None, :] < cache_len[:, None]          # [B,S]
    iq = indexer_query(pi, x)
    sc = indexer_scores(iq, idx_keys)                            # [B,Q,S]
    k = min(cfg.dsa.index_topk, S)
    ids = topk_ids(sc, k, valid[:, None, :])                     # [B,Q,K]
    # decode: Q small; gather per batch row using the *last* query's ids
    # (Q>1 MTP drafts share the union via per-q gather)
    q_comb = absorbed_query(p, cfg, x, positions)                # [B,Q,H,D]
    if use_kernel:
        from repro.kernels.sparse_mla import ops as sk_ops
        out_lat = sk_ops.sparse_mla_gather_attend(
            q_comb, latent_cache, ids, valid, mla_scale(cfg),
            cfg.mla.kv_lora_rank)
    else:
        B, Q, K = ids.shape
        gl = jnp.take_along_axis(latent_cache[:, None], ids[..., None], axis=2)
        gv = ids < cache_len[:, None, None]                      # [B,Q,K]
        s = jnp.einsum("bqhd,bqkd->bqhk", q_comb.astype(jnp.float32),
                       gl.astype(jnp.float32)) * mla_scale(cfg)
        s = jnp.where(gv[:, :, None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out_lat = jnp.einsum(
            "bqhk,bqkv->bqhv", w,
            gl[..., :cfg.mla.kv_lora_rank].astype(jnp.float32)
        ).astype(x.dtype)
    return output_proj(p, cfg, out_lat), ids


# ---------------------------------------------------------------------------
# Prefill / train: chunked masked attention with DSA selection
# ---------------------------------------------------------------------------

def dsa_threshold(sc: jax.Array, k: int, valid: jax.Array) -> jax.Array:
    """Per-row k-th largest indexer score (selection threshold). [B,Q]"""
    sc = jnp.where(valid, sc, NEG_INF)
    kk = min(k, sc.shape[-1])
    vals, _ = jax.lax.top_k(sc, kk)
    return vals[..., -1]


def dsa_keep_mask(sc: jax.Array, k: int, valid: jax.Array) -> jax.Array:
    """Exact top-k membership mask [..., S] with ``lax.top_k`` tie
    semantics (lowest index wins among equal scores).

    A ``sc >= threshold`` mask admits *every* tie at the k-th score — and
    the relu'd indexer produces many exact-0.0 ties — so thresholding
    attends to more than k entries while the decode/serve paths gather
    exactly k.  All DSA paths (train, prefill, decode) select through this
    same top-k set so their outputs agree up to fp reassociation."""
    sc = jnp.where(valid, sc, NEG_INF)
    kk = min(k, sc.shape[-1])
    _, ids = jax.lax.top_k(sc, kk)
    keep = jnp.zeros(sc.shape, bool)
    keep = jnp.put_along_axis(keep, ids, True, axis=-1, inplace=False)
    return keep & valid


def mla_train_attend(p: dict, pi: Optional[dict], cfg: ArchConfig,
                     x: jax.Array, positions: jax.Array) -> jax.Array:
    """Dense differentiable MLA (+DSA top-k mask) for train_4k shapes."""
    m = cfg.mla
    B, S, _ = x.shape
    lat = latent_entries(p, cfg, x, positions)                   # [B,S,D]
    q_comb = absorbed_query(p, cfg, x, positions)                # [B,S,H,D]
    q_comb = shard(q_comb, "batch", None, "heads", None)
    s = jnp.einsum("bqhd,bkd->bhqk", q_comb.astype(jnp.float32),
                   lat.astype(jnp.float32)) * mla_scale(cfg)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    bias = jnp.where(causal, 0.0, NEG_INF)
    if pi is not None and cfg.dsa is not None and cfg.dsa.index_topk < S:
        iq = indexer_query(pi, x)
        sc = indexer_scores(iq, indexer_keys(pi, x))             # [B,Q,S]
        keep = dsa_keep_mask(sc, cfg.dsa.index_topk, causal[:, 0])
        bias = bias + jnp.where(keep[:, None], 0.0, NEG_INF)
    w = jax.nn.softmax(s + bias, axis=-1)
    o_lat = jnp.einsum("bhqk,bkv->bqhv", w,
                       lat[..., :m.kv_lora_rank].astype(jnp.float32))
    return output_proj(p, cfg, o_lat.astype(x.dtype))


def mla_prefill_attend(p: dict, pi: Optional[dict], cfg: ArchConfig,
                       x: jax.Array, positions: jax.Array,
                       kv_block: int = 2048
                       ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Chunked-flash MLA prefill (+DSA threshold mask).

    Returns (out [B,S,d], latent cache [B,S,D], indexer keys or None).
    Two passes when DSA is on: (1) chunked indexer top-k threshold,
    (2) chunked online-softmax attention with the >=threshold mask.
    """
    m = cfg.mla
    B, S, _ = x.shape
    kv_block = min(kv_block, S)
    pad = (-S) % kv_block
    Sp = S + pad
    lat = latent_entries(p, cfg, x, positions)
    q_comb = absorbed_query(p, cfg, x, positions)
    q_comb = shard(q_comb, "batch", None, "heads", None)
    H = q_comb.shape[2]

    ikeys = None
    thr = None
    n_tie = None
    iq = None
    if pi is not None and cfg.dsa is not None and cfg.dsa.index_topk < S:
        ikeys = indexer_keys(pi, x)
        iq = indexer_query(pi, x)
        # pass 1: streaming top-k threshold via per-block running top-k
        k = cfg.dsa.index_topk

        def tb(carry, blk):
            topv = carry
            kc, pc = blk
            sc = indexer_scores(iq, kc)                          # [B,S,kb]
            okc = pc[None, None, :] <= positions[:, :, None]
            sc = jnp.where(okc, sc, NEG_INF)
            allv = jnp.concatenate([topv, sc], axis=-1)
            topv, _ = jax.lax.top_k(allv, k)
            return topv, None

        nb = Sp // kv_block
        ik_p = jnp.pad(ikeys, ((0, 0), (0, pad), (0, 0))) if pad else ikeys
        pos_p1 = jnp.pad(positions, ((0, 0), (0, pad)),
                         constant_values=2 ** 30) if pad else positions
        kb_keys = ik_p.reshape(B, nb, kv_block, -1).transpose(1, 0, 2, 3)
        kb_pos = pos_p1.reshape(B, nb, kv_block).transpose(1, 0, 2)[:, 0]
        top0 = jnp.full((B, S, cfg.dsa.index_topk), NEG_INF, jnp.float32)
        topv, _ = jax.lax.scan(tb, top0, (kb_keys, kb_pos))
        thr = topv[..., -1]                                      # [B,S]
        # exact top-k, lax.top_k tie semantics: besides every score > thr,
        # keep only the first (index order) n_tie scores == thr — a plain
        # ">= thr" mask would admit *all* ties (the relu'd indexer emits
        # many exact-0.0 scores) and diverge from the decode-side gather
        n_tie = k - (topv > thr[..., None]).sum(-1)              # [B,S]

    # pass 2: chunked online-softmax over latent blocks
    nb = Sp // kv_block
    lat_p = jnp.pad(lat, ((0, 0), (0, pad), (0, 0))) if pad else lat
    pos_p = jnp.pad(positions, ((0, 0), (0, pad)),
                    constant_values=2 ** 30) if pad else positions
    ik_p2 = (jnp.pad(ikeys, ((0, 0), (0, pad), (0, 0)))
             if (ikeys is not None and pad) else ikeys)
    lat_b = lat_p.reshape(B, nb, kv_block, -1).transpose(1, 0, 2, 3)
    pos_b = pos_p.reshape(B, nb, kv_block).transpose(1, 0, 2)
    ik_b = (ik_p2.reshape(B, nb, kv_block, -1).transpose(1, 0, 2, 3)
            if ik_p2 is not None else jnp.zeros((nb, B, kv_block, 1), x.dtype))

    def body(carry, blk):
        mx, l, acc, tie_seen = carry
        lc, pc, kc = blk
        s = jnp.einsum("bqhd,bkd->bhqk", q_comb.astype(jnp.float32),
                       lc.astype(jnp.float32)) * mla_scale(cfg)
        ok = pc[:, None, None, :] <= positions[:, None, :, None]
        if thr is not None:
            sc = indexer_scores(iq, kc)                          # [B,S,kb]
            okq = pc[:, None, :] <= positions[:, :, None]        # [B,S,kb]
            gt = (sc > thr[..., None]) & okq
            eq = (sc == thr[..., None]) & okq
            # running index-order rank of threshold ties across blocks
            rank = tie_seen[..., None] + \
                jnp.cumsum(eq.astype(jnp.int32), axis=-1) - eq
            keep = gt | (eq & (rank < n_tie[..., None]))
            tie_seen = tie_seen + eq.sum(axis=-1)
            ok &= keep[:, None]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(mx, s.max(axis=-1))
        pw = jnp.exp(s - m_new[..., None])
        pw = jnp.where(ok, pw, 0.0)
        corr = jnp.exp(mx - m_new)
        l_new = l * corr + pw.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkv->bhqv", pw, lc[..., :m.kv_lora_rank].astype(jnp.float32))
        return (m_new, l_new, acc_new, tie_seen), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, m.kv_lora_rank), jnp.float32)
    t0 = jnp.zeros((B, S), jnp.int32)
    (mx, l, acc, _), _ = jax.lax.scan(body, (m0, l0, a0, t0),
                                      (lat_b, pos_b, ik_b))
    o_lat = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(0, 2, 1, 3)
    out = output_proj(p, cfg, o_lat.astype(x.dtype))
    return out, lat, ikeys
