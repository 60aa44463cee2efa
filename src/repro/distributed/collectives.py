"""Distributed attention helpers: exact cross-shard flash-decode merge.

For long_500k the cache sequence dim is sharded; each shard computes a
flash partial over its local chunk and the merge is an exact psum-style
renormalization — the distributed analogue of ESS's Attn0/Attn1 merge.
Used by shard_map-based serving variants and validated in tests against
the single-device oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def local_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                  valid: jax.Array, scale: float):
    """One shard's flash statistics. q [B,H,D], k/v [B,Sl,D], valid [B,Sl].
    Returns (o [B,H,Dv], m [B,H], l [B,H]) unnormalized."""
    s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    o = jnp.einsum("bhs,bsd->bhd", p, v.astype(jnp.float32))
    return o, m, l


def merge_across(axis: str, o: jax.Array, m: jax.Array, l: jax.Array
                 ) -> jax.Array:
    """Exact renormalized merge over a mesh axis (inside shard_map)."""
    m_max = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_max)
    o_sum = jax.lax.psum(o * corr[..., None], axis)
    l_sum = jax.lax.psum(l * corr, axis)
    return o_sum / jnp.maximum(l_sum, 1e-30)[..., None]


def sharded_flash_decode(mesh, axis: str, q, k_sharded, v_sharded, valid,
                         scale: float):
    """shard_map wrapper: q replicated, k/v/valid sharded on seq."""
    from jax.sharding import PartitionSpec as P

    def prog(qq, kk, vv, vd):
        o, m, l = local_partial(qq, kk, vv, vd, scale)
        return merge_across(axis, o, m, l)

    return jax.shard_map(
        prog, mesh=mesh,
        in_specs=(P(), P(None, axis, None), P(None, axis, None),
                  P(None, axis)),
        out_specs=P(), check_vma=False)(q, k_sharded, v_sharded, valid)
