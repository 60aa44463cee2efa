"""Host-tier placement + the FlashTrans-analogue transfer engine (paper §3.1).

GPU version: UVA lets the kernel dereference pinned host memory, coalescing
656 B fragments.  TPU/JAX version: the full Latent-Cache lives in a
``pinned_host`` memory-space buffer; the *gather of scattered rows runs on
the host* (``compute_on('device_host')``) and exactly one dense
``[M, D]``-row DMA crosses PCIe per layer per step — the same
transaction-coalescing effect FlashTrans achieves with UVA.  The naive
baseline (per-row ``dynamic_slice`` + copy, ~0.79 GB/s in the paper's
measurement) is modelled in the simulator for comparison.

Outside a mesh/jit context everything degrades to plain device arrays so
unit tests run on CPU without memory-space plumbing.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental.compute_on import compute_on
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import compression as cmp
from repro.distributed import sharding as shd


def host_available() -> bool:
    """True iff the default device exposes a ``pinned_host`` memory."""
    try:
        kinds = [m.kind for m in jax.devices()[0].addressable_memories()]
    except jax.errors.JaxRuntimeError:   # backend without memory kinds
        return False
    return "pinned_host" in kinds


def host_sharding(*axes, fallback_device: bool = False):
    """NamedSharding with pinned_host memory kind under the active ctx."""
    ctx = shd.current()
    if ctx is None or ctx.mesh is None:
        return None
    kind = "pinned_host" if not fallback_device else "device"
    return ctx.sharding(*axes, memory_kind=kind)


def host_sharding_for(shape, axes):
    """Shape-aware host sharding (prunes axes that don't divide — e.g.
    batch=1 long-context cells can't take the data axis)."""
    ctx = shd.current()
    if ctx is None or ctx.mesh is None:
        return None
    return ctx.sharding_for(tuple(shape), axes, memory_kind="pinned_host")


def to_host(x: jax.Array, *axes) -> jax.Array:
    s = host_sharding_for(x.shape, axes)
    if s is None:
        return x
    return jax.device_put(x, s)


def to_device(x: jax.Array, *axes) -> jax.Array:
    ctx = shd.current()
    if ctx is None or ctx.mesh is None:
        return x
    return jax.device_put(x, ctx.sharding(*axes))


def _paged_phys(ids: jax.Array, block_table: jax.Array, page_rows: int,
                num_pages: int, batch_offset
                ) -> tuple[jax.Array, jax.Array]:
    """Translate sequence positions -> physical pool rows via block tables.

    ids [B,M] (sequence positions, -1 padding), block_table [B_total, NB].
    ``batch_offset`` may be a Python int or a traced i32 scalar (the
    compiled serve-round programs pass the admitting slot dynamically so
    one program serves every slot without retracing).
    Returns (phys [B,M] rows into the flat [NP*R, D] pool view,
    valid [B,M] — in-range *and* mapped)."""
    B = ids.shape[0]
    bt = jax.lax.dynamic_slice_in_dim(block_table, batch_offset, B, axis=0)
    cap = bt.shape[1] * page_rows
    safe = jnp.clip(ids, 0, cap - 1)
    page = jnp.take_along_axis(bt, safe // page_rows, axis=1)      # [B,M]
    valid = (ids >= 0) & (ids < cap) & (page >= 0)
    phys = jnp.clip(page, 0, num_pages - 1) * page_rows + safe % page_rows
    return phys, valid


def host_gather_rows(host_cache: jax.Array, ids: jax.Array, *,
                     layer: int = 0, batch_offset: int = 0,
                     block_table: jax.Array | None = None,
                     axes_out=("cache_batch", None, None)) -> jax.Array:
    """FlashTrans fetch: ids [B,M] (-1 padding) -> rows [B,M,D] on device.

    Two host-tier layouts:

    * dense — host_cache [B,S,D] or [L,B,S,D] (pinned_host), positions
      index the slot's own row range;
    * paged (``block_table`` given) — host_cache [NP,R,D] or [L,NP,R,D]
      global page pool; positions route through the slot's block table to
      physical pool rows, unmapped pages read as zero.

    The gather executes in the host memory space; the index pairs are
    packed on the *device* and shipped to the host, so the host computation
    is exactly one ``lax.gather`` — no auxiliary iota or bounds constants
    can land in the wrong memory space, and the SPMD partitioner keeps
    everything batch-sharded (verified: zero host-buffer all-gathers).
    Only the packed [B,M,D] result is DMA'd to the device — one coalesced
    transaction instead of M fragmented ones (the FlashTrans effect).
    """
    ctx = shd.current()
    B, M = ids.shape
    D = host_cache.shape[-1]

    if block_table is not None:
        R = host_cache.shape[-2]
        NP = host_cache.shape[-3]
        phys, valid = _paged_phys(ids, block_table, R, NP, batch_offset)
        if ctx is None or ctx.mesh is None:
            cl = host_cache[layer] if host_cache.ndim == 4 else host_cache
            rows = jnp.take(cl.reshape(NP * R, D), phys, axis=0)
            return jnp.where(valid[..., None], rows, 0)

        idx_h = jax.device_put(phys[..., None], host_sharding_for(
            (B, M, 1), ("cache_batch", None, None)))
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0,),
            start_index_map=(0,))

        @compute_on("device_host")
        @jax.jit
        def _gather_paged(c, i):
            cl = c[layer] if c.ndim == 4 else c
            return jax.lax.gather(
                cl.reshape(NP * R, D), i, dn, (1, D),
                mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

        rows = _gather_paged(host_cache, idx_h)
        rows = jax.device_put(rows, ctx.sharding_for((B, M, D), axes_out))
        return jnp.where(valid[..., None], rows, 0)

    S = host_cache.shape[-2]
    safe = jnp.clip(ids, 0, S - 1)
    if ctx is None or ctx.mesh is None:
        cl = host_cache[layer] if host_cache.ndim == 4 else host_cache
        cl = jax.lax.dynamic_slice_in_dim(cl, batch_offset, B, axis=0)
        rows = jnp.take_along_axis(cl, safe[..., None], axis=1)
        return jnp.where((ids >= 0)[..., None], rows, 0)

    bi = jax.lax.broadcasted_iota(jnp.int32, (B, M), 0) + batch_offset
    idx2 = jnp.stack([bi, safe], axis=-1)
    idx2_h = jax.device_put(idx2, host_sharding_for(
        idx2.shape, ("cache_batch", None, None)))
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(2,), collapsed_slice_dims=(0, 1),
        start_index_map=(0, 1))

    @compute_on("device_host")
    @jax.jit
    def _gather(c, i):
        cl = c[layer] if c.ndim == 4 else c
        return jax.lax.gather(cl, i, dn, (1, 1, D),
                              mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)

    rows = _gather(host_cache, idx2_h)
    rows = jax.device_put(rows, ctx.sharding_for((B, M, D), axes_out))
    return jnp.where((ids >= 0)[..., None], rows, 0)


def host_scatter_rows(host_cache: jax.Array, ids: jax.Array,
                      rows: jax.Array, *, slot_mask: jax.Array | None,
                      layer: int = 0, batch_offset: int = 0,
                      block_table: jax.Array | None = None) -> jax.Array:
    """D2H writeback: scatter rows [B,Q,D] into the host cache at ids
    [B,Q] (sequence positions; -1 = masked).  Returns the functionally
    updated full cache (XLA aliases the host buffer in place when the step
    donates its caches).

    ``slot_mask`` is **required, keyword-only** (the serve loop's live-slot
    contract: an un-gated scatter from a freed or mid-prefill slot is
    exactly the page-0 aliasing bug class — see ANALYSIS.md ESS001).
    ``slot_mask=None`` states explicitly that every batch row is live (or
    that the caller already folded the mask into ``ids``); a ``[B]`` bool
    mask drops the writes of masked rows in-step.

    With ``block_table`` the positions route through the paged
    indirection; writes to unmapped pages are dropped.  Masked rows are
    otherwise handled read-modify-write (rewrite the current value), so no
    copy of the huge host buffer is ever materialized."""
    ctx = shd.current()
    if slot_mask is not None:
        ids = jnp.where(slot_mask[:, None], ids, -1)
    B, Q = ids.shape

    if block_table is not None:
        R = host_cache.shape[-2]
        NP = host_cache.shape[-3]
        D = host_cache.shape[-1]
        phys, valid = _paged_phys(ids, block_table, R, NP, batch_offset)
        if ctx is None or ctx.mesh is None:
            cl = host_cache[layer] if host_cache.ndim == 4 else host_cache
            flat = cl.reshape(NP * R, D)
            tgt = jnp.where(valid, phys, NP * R)         # OOB -> drop
            flat2 = flat.at[tgt].set(rows.astype(cl.dtype), mode="drop")
            cl2 = flat2.reshape(NP, R, D)
            return (host_cache.at[layer].set(cl2) if host_cache.ndim == 4
                    else cl2)

        # masked/unmapped rows are routed to an out-of-bounds sentinel and
        # dropped — a clipped target (page 0 row 0) would alias a *live*
        # slot's physical row, and a duplicate-index scatter against that
        # slot's own append leaves the winner unspecified
        tgt = jnp.where(valid, phys, NP * R)
        ax2 = host_sharding_for(tgt.shape, ("cache_batch", None))
        tgt_h = jax.device_put(tgt, ax2)
        rows_h = jax.device_put(rows.astype(host_cache.dtype),
                                host_sharding_for(
                                    rows.shape, ("cache_batch", None, None)))

        @compute_on("device_host")
        @jax.jit
        def _scatter_paged(c, i, r):
            cl = c[layer] if c.ndim == 4 else c
            flat = cl.reshape(NP * R, D)
            flat2 = flat.at[i].set(r, mode="drop")
            cl2 = flat2.reshape(NP, R, D)
            if c.ndim == 4:
                return jax.lax.dynamic_update_slice_in_dim(c, cl2[None],
                                                           layer, axis=0)
            return cl2

        return _scatter_paged(host_cache, tgt_h, rows_h)

    S = host_cache.shape[-2]
    valid = ids >= 0
    safe = jnp.clip(ids, 0, S - 1)
    if ctx is None or ctx.mesh is None:
        cl = host_cache[layer] if host_cache.ndim == 4 else host_cache
        cl_s = jax.lax.dynamic_slice_in_dim(cl, batch_offset, B, axis=0)
        cur = jnp.take_along_axis(cl_s, safe[..., None], axis=1)
        r2 = jnp.where(valid[..., None], rows.astype(cl.dtype), cur)
        bi = jnp.arange(B)[:, None]
        cl2_s = cl_s.at[bi, safe].set(r2)
        cl2 = jax.lax.dynamic_update_slice_in_dim(cl, cl2_s, batch_offset,
                                                  axis=0)
        return (host_cache.at[layer].set(cl2) if host_cache.ndim == 4
                else cl2)

    bi = jax.lax.broadcasted_iota(jnp.int32, (B, Q), 0) + batch_offset
    ax2 = host_sharding_for(bi.shape, ("cache_batch", None))
    bi_h = jax.device_put(bi, ax2)
    ids_h = jax.device_put(safe, ax2)
    valid_h = jax.device_put(valid, ax2)
    rows_h = jax.device_put(rows.astype(host_cache.dtype), host_sharding_for(
        rows.shape, ("cache_batch", None, None)))

    @compute_on("device_host")
    @jax.jit
    def _scatter(c, b2, i, v, r):
        cl = c[layer] if c.ndim == 4 else c
        cur = cl.at[b2, i].get(mode="promise_in_bounds")
        r2 = jnp.where(v[..., None], r, cur)
        cl2 = cl.at[b2, i].set(r2, mode="promise_in_bounds")
        if c.ndim == 4:
            return jax.lax.dynamic_update_slice_in_dim(c, cl2[None], layer,
                                                       axis=0)
        return cl2

    return _scatter(host_cache, bi_h, ids_h, valid_h, rows_h)


def host_scatter_rows_stacked(host_cache: jax.Array, ids: jax.Array,
                              rows: jax.Array, *,
                              slot_mask: jax.Array | None,
                              batch_offset: int = 0,
                              block_table: jax.Array | None = None
                              ) -> jax.Array:
    """Scatter rows [L,B,Q,D] at the *same* positions ids [B,Q] into every
    layer of a stacked host cache in one pass (admission graft: the target
    pages are identical per layer, so L separate per-layer scatters would
    functionally rewrite the full pool L times).

    ``slot_mask`` is required keyword-only, exactly as in
    :func:`host_scatter_rows` (ESS001)."""
    ctx = shd.current()
    if slot_mask is not None:
        ids = jnp.where(slot_mask[:, None], ids, -1)
    Lh = host_cache.shape[0]
    if ctx is not None and ctx.mesh is not None:
        # mesh path: fall back to the per-layer host-compute scatter
        out = host_cache
        for layer in range(Lh):
            out = host_scatter_rows(out, ids, rows[layer], slot_mask=None,
                                    layer=layer, batch_offset=batch_offset,
                                    block_table=block_table)
        return out
    B, Q = ids.shape
    D = host_cache.shape[-1]
    if block_table is not None:
        NP, R = host_cache.shape[1], host_cache.shape[2]
        phys, valid = _paged_phys(ids, block_table, R, NP, batch_offset)
        flat = host_cache.reshape(Lh, NP * R, D)
        tgt = jnp.where(valid, phys, NP * R)             # OOB -> drop
        flat2 = flat.at[:, tgt].set(
            rows.astype(host_cache.dtype), mode="drop")
        return flat2.reshape(Lh, NP, R, D)
    S = host_cache.shape[-2]
    valid = (ids >= 0) & (ids < S)
    bi = jnp.broadcast_to(jnp.arange(B)[:, None] + batch_offset, ids.shape)
    bi = jnp.where(valid, bi, host_cache.shape[1])       # OOB -> drop
    safe = jnp.clip(ids, 0, S - 1)
    return host_cache.at[:, bi, safe].set(
        rows.astype(host_cache.dtype), mode="drop")


def gather_into_slab(host_cache: jax.Array, ids: jax.Array, *,
                     slot_mask: jax.Array | None, batch_offset: int = 0,
                     block_table: jax.Array | None = None) -> jax.Array:
    """Async-offload staging gather: the H2D half of the split transfer.

    ``ids [L,B,P]`` are *per-layer* predicted positions (``-1`` = not
    staged); the result ``[L,B,P,D]`` is the device-resident landing slab
    round ``N+1`` computes against.  Each layer routes through the same
    FlashTrans gather as the synchronous fetch, so a staged row is
    bit-identical to what the fallback would read — speculation can be
    wasted, never wrong.

    ``slot_mask`` is required keyword-only (ESS001): staging rows for a
    frozen slot would land the previous occupant's pages in the slab."""
    if slot_mask is not None:
        ids = jnp.where(slot_mask[None, :, None], ids, -1)
    return jnp.stack([
        host_gather_rows(host_cache, ids[layer], layer=layer,
                         batch_offset=batch_offset,
                         block_table=block_table)
        for layer in range(ids.shape[0])])


def scatter_from_slab(host_cache: jax.Array, ids: jax.Array,
                      rows: jax.Array, *, slot_mask: jax.Array | None,
                      batch_offset: int = 0,
                      block_table: jax.Array | None = None) -> jax.Array:
    """Async-offload spill flush: the D2H half of the split transfer.

    ``rows [L,B,Q,D]`` is the round's spill slab — every layer's freshly
    appended latents, collected during compute and committed in **one**
    stacked scatter at the commit stage (the synchronous round pays L
    per-layer functional pool rewrites instead).  Positions ``ids
    [B,Q]`` are shared across layers; ``-1`` rows drop.

    ``slot_mask`` is required keyword-only (ESS001), exactly as in
    :func:`host_scatter_rows`."""
    return host_scatter_rows_stacked(host_cache, ids, rows,
                                     slot_mask=slot_mask,
                                     batch_offset=batch_offset,
                                     block_table=block_table)


# ---------------------------------------------------------------------------
# Quantized-tier wrappers: dequant-on-gather / quantize-on-scatter
# ---------------------------------------------------------------------------
# The quantized host tier is two pinned-host arrays moved by the *same*
# FlashTrans machinery above: the int8/fp8 payload [.., NP, R, D] and a
# per-page scale vector [.., NP, R, 1] (one SCALE_DTYPE scale per row —
# see repro.distributed.compression).  Every transfer crosses PCIe
# compressed; bf16 rows only ever materialize at miss width, on device,
# after the DMA (the ESS106 audit proves no cache-tier-sized upcast
# survives into any StepProgram).


def gather_tier_rows(host_cache: jax.Array, host_scales: jax.Array | None,
                     ids: jax.Array, *, layer: int = 0,
                     batch_offset: int = 0,
                     block_table: jax.Array | None = None,
                     out_dtype=None) -> jax.Array:
    """Scale-aware fetch: ids [B,M] -> dequantized rows [B,M,D] on device.

    ``host_scales is None`` is the raw bf16 tier (identical to
    :func:`host_gather_rows`).  Quantized tiers gather the payload and the
    scale column through two host-compute gathers — both DMAs move
    compressed data — and dequantize at **miss width** on device.  Masked
    ids return exact zeros either way (payload 0 x scale 0)."""
    rows = host_gather_rows(host_cache, ids, layer=layer,
                            batch_offset=batch_offset,
                            block_table=block_table)
    if host_scales is None:
        return rows if out_dtype is None else rows.astype(out_dtype)
    srows = host_gather_rows(host_scales, ids, layer=layer,
                             batch_offset=batch_offset,
                             block_table=block_table)
    return cmp.dequantize_rows(rows, srows,
                               jnp.bfloat16 if out_dtype is None
                               else out_dtype)


def scatter_tier_rows(host_cache: jax.Array, host_scales: jax.Array | None,
                      ids: jax.Array, rows: jax.Array, *,
                      slot_mask: jax.Array | None, layer: int = 0,
                      batch_offset: int = 0,
                      block_table: jax.Array | None = None
                      ) -> tuple[jax.Array, jax.Array | None]:
    """Quantize-on-scatter writeback; returns ``(cache', scales')``.

    Quantization happens on device at **append width** ([B,Q,D]); only the
    one-byte payload and the scale column cross PCIe.  ``slot_mask`` is
    required keyword-only exactly as in :func:`host_scatter_rows`
    (ESS001)."""
    if host_scales is None:
        return host_scatter_rows(host_cache, ids, rows, slot_mask=slot_mask,
                                 layer=layer, batch_offset=batch_offset,
                                 block_table=block_table), None
    q, s = cmp.quantize_rows(rows, host_cache.dtype)
    cache2 = host_scatter_rows(host_cache, ids, q, slot_mask=slot_mask,
                               layer=layer, batch_offset=batch_offset,
                               block_table=block_table)
    scales2 = host_scatter_rows(host_scales, ids, s, slot_mask=slot_mask,
                                layer=layer, batch_offset=batch_offset,
                                block_table=block_table)
    return cache2, scales2


def scatter_tier_rows_stacked(host_cache: jax.Array,
                              host_scales: jax.Array | None,
                              ids: jax.Array, rows: jax.Array, *,
                              slot_mask: jax.Array | None,
                              batch_offset: int = 0,
                              block_table: jax.Array | None = None
                              ) -> tuple[jax.Array, jax.Array | None]:
    """All-layer quantize-on-scatter (admission graft / prefill flush):
    rows [L,B,Q,D] quantized per row on device, then one stacked payload
    scatter + one stacked scale scatter.  Returns ``(cache', scales')``."""
    if host_scales is None:
        return host_scatter_rows_stacked(
            host_cache, ids, rows, slot_mask=slot_mask,
            batch_offset=batch_offset, block_table=block_table), None
    q, s = cmp.quantize_rows(rows, host_cache.dtype)
    cache2 = host_scatter_rows_stacked(host_cache, ids, q,
                                       slot_mask=slot_mask,
                                       batch_offset=batch_offset,
                                       block_table=block_table)
    scales2 = host_scatter_rows_stacked(host_scales, ids, s,
                                        slot_mask=slot_mask,
                                        batch_offset=batch_offset,
                                        block_table=block_table)
    return cache2, scales2


def abstract_host(shape, dtype, *axes):
    """ShapeDtypeStruct pinned to host for the dry-run."""
    ctx = shd.current()
    if ctx is None or ctx.mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=ctx.sharding_for(shape, axes, memory_kind="pinned_host"))
