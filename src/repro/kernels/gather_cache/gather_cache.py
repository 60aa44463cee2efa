"""FlashTrans-analogue gather kernel (paper §3.1, adapted to TPU).

The paper's FlashTrans uses UVA so the GPU coalesces 656 B scattered
Latent-Cache rows out of CPU memory.  The TPU analogue at the *device* tier:
rows are scattered across a big HBM-resident pool and must be packed into a
dense VMEM-friendly buffer for the attention kernel.  Scalar-prefetched
indices drive the BlockSpec ``index_map``, so each grid step DMAs exactly
the requested row — the Pallas pipeline overlaps the row DMAs with the
copy-out, which is the in-kernel version of FlashTrans's transaction
coalescing.

Host→device traffic itself is handled by ``repro.core.offload`` (memory
spaces); this kernel covers the on-device pool→contiguous packing that both
Attn0 (pool hits) and the LRU admission path need.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pad_dim, round_up

ROW_BLOCK = 8   # output rows per (8, D) output tile
TILE = 8        # HBM row tiling: the smallest row block the TPU can DMA


def _place_row(ids_ref, tile_ref, out_ref, scale=None) -> jax.Array:
    """Grid step ``i`` fetched the TILE-row block holding row ``ids[i]``;
    return the output tile with that row (times ``scale``, if given) in
    place ``i % ROW_BLOCK``, in f32.  A max over the block with every
    other row at -inf selects exactly — it keeps -0.0 and the row's
    payload — and needs no dynamic sublane indexing."""
    i = pl.program_id(0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)
    row = jnp.where(sub == ids_ref[i] % TILE,
                    tile_ref[...].astype(jnp.float32),
                    -jnp.inf).max(axis=0, keepdims=True)        # [1, D]
    if scale is not None:
        row = row * scale                                        # [RB, D]
    dst = jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, 1), 0)
    return jnp.where(dst == i % ROW_BLOCK, row,
                     out_ref[...].astype(jnp.float32))


def _row_gather_call(kernel, cache, ids, scales, out_dtype, interpret):
    """Shared pallas_call plumbing of the row gathers.

    The TPU lowering refuses a ``(1, D)`` block (the second-minor block
    dim must be a multiple of 8) and a manual DMA of a D=576 row (a
    slice of the lane-padded minor dim is unaligned).  So grid step
    ``i`` takes the TILE-row block that holds row ``ids[i]`` through the
    BlockSpec pipeline (the next block's DMA overlaps this step), and
    ROW_BLOCK consecutive steps fill one output tile.  Ids are clipped
    into range and padded to whole output tiles; cache rows are padded
    to whole TILEs (a no-op for page-pool caches)."""
    S, D = cache.shape
    M = ids.shape[0]
    if interpret is None:
        interpret = default_interpret()
    Mp = round_up(max(M, 1), ROW_BLOCK)
    safe = jnp.pad(jnp.clip(ids, 0, S - 1), (0, Mp - M))
    cache = pad_dim(cache, 0, round_up(S, TILE))
    in_specs = [pl.BlockSpec((TILE, D), lambda i, ids_ref: (ids_ref[i] // TILE,
                                                           0))]
    args = [cache]
    if scales is not None:
        # the gathered rows' scales, widened to f32 outside the kernel
        # (M values; the TPU vector unit has no f16 loads)
        in_specs.append(pl.BlockSpec((ROW_BLOCK, 1),
                                     lambda i, ids_ref: (i // ROW_BLOCK, 0)))
        args.append(jnp.take(scales, safe, axis=0).astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Mp,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ROW_BLOCK, D),
                               lambda i, ids_ref: (i // ROW_BLOCK, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Mp, D), out_dtype),
        interpret=interpret,
    )(safe, *args)
    return out[:M]


def _gather_kernel(ids_ref, tile_ref, out_ref):
    out_ref[...] = _place_row(ids_ref, tile_ref, out_ref).astype(
        out_ref.dtype)


def gather_rows_kernel(cache: jax.Array, ids: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """cache [S, D], ids [M] int32 (negative -> row 0, masked later)
    -> out [M, D].  One requested row per grid step."""
    return _row_gather_call(_gather_kernel, cache, ids, None, cache.dtype,
                            interpret)


def _gather_dequant_kernel(ids_ref, tile_ref, scales_ref, out_ref):
    # fused dequant at block width: the int8/fp8 payload never becomes a
    # wide tensor outside this tile (contract ESS106)
    out_ref[...] = _place_row(ids_ref, tile_ref, out_ref,
                              scales_ref[...]).astype(out_ref.dtype)


def gather_rows_dequant_kernel(cache: jax.Array, scales: jax.Array,
                               ids: jax.Array, out_dtype=jnp.bfloat16,
                               interpret: bool | None = None) -> jax.Array:
    """Quantized-tier row gather: cache [S, D] int8/fp8, scales [S, 1],
    ids [M] int32 -> out [M, D] ``out_dtype``.  The DMAs move the
    compressed payload; dequant runs on the tile inside the kernel."""
    return _row_gather_call(_gather_dequant_kernel, cache, ids, scales,
                            out_dtype, interpret)


def _gather_block_kernel(base_ref, cache_ref, out_ref):
    out_ref[...] = cache_ref[...]


def gather_row_blocks_kernel(cache: jax.Array, block_ids: jax.Array,
                             block_rows: int,
                             interpret: bool | None = None) -> jax.Array:
    """Paged variant: gather whole row-blocks (pages).  cache [S, D] with
    S % block_rows == 0, block_ids [NB] -> out [NB*block_rows, D].

    This is the PagedAttention-style page fetch; ESS uses it when the pool
    is managed at page granularity instead of single entries."""
    S, D = cache.shape
    NB = block_ids.shape[0]
    if interpret is None:
        interpret = default_interpret()
    safe = jnp.clip(block_ids, 0, S // block_rows - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NB,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i, ids: (ids[i], 0))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i, ids: (i, 0)),
    )
    return pl.pallas_call(
        _gather_block_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NB * block_rows, D), cache.dtype),
        interpret=interpret,
    )(safe, cache)


def _gather_block_dequant_kernel(base_ref, cache_ref, scales_ref, out_ref):
    out_ref[...] = (cache_ref[...].astype(jnp.float32) * scales_ref[...]
                    ).astype(out_ref.dtype)


def gather_row_blocks_dequant_kernel(cache: jax.Array, scales: jax.Array,
                                     block_ids: jax.Array, block_rows: int,
                                     out_dtype=jnp.bfloat16,
                                     interpret: bool | None = None
                                     ) -> jax.Array:
    """Quantized paged variant: whole-page fetch + per-row dequant.
    cache [S, D] int8/fp8 with S % block_rows == 0, scales [S, 1],
    block_ids [NB] -> out [NB*block_rows, D] ``out_dtype``.  Each grid
    step DMAs one compressed page and widens only that (block_rows, D)
    tile; the requested pages' scale columns (NB*block_rows values) are
    gathered and widened to f32 outside the kernel — the TPU vector unit
    has no f16 loads."""
    S, D = cache.shape
    NB = block_ids.shape[0]
    if interpret is None:
        interpret = default_interpret()
    safe = jnp.clip(block_ids, 0, S // block_rows - 1)
    sc = jnp.take(scales.reshape(S // block_rows, block_rows), safe, axis=0
                  ).astype(jnp.float32).reshape(NB * block_rows, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NB,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i, ids: (ids[i], 0)),
                  pl.BlockSpec((block_rows, 1), lambda i, ids: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i, ids: (i, 0)),
    )
    return pl.pallas_call(
        _gather_block_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NB * block_rows, D), out_dtype),
        interpret=interpret,
    )(safe, cache, sc)
