"""GPipe-style pipeline parallelism over the ``pod`` axis (shard_map).

For multi-pod training where the pod interconnect (DCN) is much slower
than ICI, pipelining the *layer stack* across pods trades the per-step DP
all-reduce over DCN for thin ``collective_permute`` activations between
stage boundaries.

Schedule: GPipe with M microbatches — stage s processes microbatch m at
tick t = s + m; bubbles = (S-1)/(M+S-1).  Implemented as a lax.scan over
ticks inside shard_map; every stage runs the same program (SPMD) with its
own stage slice of the stacked layer params.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stacked_params, x: jax.Array,
                   mesh: jax.sharding.Mesh, *, axis: str = "pod",
                   microbatches: int = 4) -> jax.Array:
    """Run layers split into ``n_stages = size(axis)`` contiguous stages.

    layer_fn(layer_params, x_micro) -> x_micro; stacked_params leaves are
    [L, ...] with L % n_stages == 0; x [B, ...] with B % microbatches == 0.
    """
    n_stages = mesh.devices.shape[list(mesh.axis_names).index(axis)]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    B = x.shape[0]
    assert B % microbatches == 0

    def stage_program(sparams, xin):
        # sparams: this stage's [L/n_stages, ...] slice; xin [1, B, ...]
        idx = jax.lax.axis_index(axis)
        xin = xin[0]
        mb = xin.reshape((microbatches, B // microbatches) + xin.shape[1:])
        n_ticks = microbatches + n_stages - 1

        def run_stage(xm):
            def body(c, lp):
                return layer_fn(lp, c), None
            out, _ = jax.lax.scan(body, xm, sparams)
            return out

        def tick(carry, t):
            buf_in, out_buf = carry
            m = t - idx                      # microbatch this stage works on
            active = (m >= 0) & (m < microbatches)
            mc = jnp.clip(m, 0, microbatches - 1)
            xm = jax.lax.dynamic_index_in_dim(buf_in, mc, 0, keepdims=False)
            ym = run_stage(xm)
            ym = jnp.where(active, ym, xm)
            # last stage collects finals; others ship downstream
            out_buf = jnp.where(
                active & (idx == n_stages - 1),
                jax.lax.dynamic_update_index_in_dim(out_buf, ym, mc, 0),
                out_buf)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            recv = jax.lax.ppermute(ym, axis, perm)
            # receiver (stage idx) stores the message from stage idx-1,
            # which just finished microbatch m_prev = t - (idx - 1)
            m_prev = t - idx + 1
            ok = (idx > 0) & (m_prev >= 0) & (m_prev < microbatches)
            mp = jnp.clip(m_prev, 0, microbatches - 1)
            buf_in = jnp.where(
                ok, jax.lax.dynamic_update_index_in_dim(buf_in, recv, mp, 0),
                buf_in)
            return (buf_in, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (mb, jnp.zeros_like(mb)), jnp.arange(n_ticks))
        # broadcast the last stage's collected outputs to every stage
        mine = jnp.where(idx == n_stages - 1, out_buf,
                         jnp.zeros_like(out_buf))
        final = jax.lax.psum(mine, axis)
        return final.reshape((1, B) + x.shape[1:])

    spec_p = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(
        stage_program, mesh=mesh,
        in_specs=(spec_p, P(axis)), out_specs=P(axis), check_vma=False)
    # replicate x to every stage's input slot (stage 0 uses it; others churn)
    xin = jnp.broadcast_to(x[None], (n_stages,) + x.shape)
    return fn(stacked_params, xin)[0]
