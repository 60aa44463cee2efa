"""Compile every Pallas kernel of the main path for a TPU v5e at real
widths — no chip needed: the TPU compiler compiles for a described
``v5e:2x2`` topology.  This catches what interpret mode cannot (block
shapes the TPU lowering refuses, loads the vector unit lacks).  Nothing
runs, so these tests say nothing about results; ``chip_smoke.py`` checks
those on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under several pytest
workers the worker that runs this file is the one that loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gather_cache import ops as gops
from repro.kernels.indexer.indexer import indexer_scores_kernel
from repro.kernels.sparse_mla import ops as sops

H, D, K, RANK = 128, 576, 2048, 512     # deepseek-v32-exp-ess MLA widths
HI, DI = 64, 128                         # DSA lightning indexer
S, M, PAGE = 8192, 256, 64               # cache rows, gathered rows, page


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


KERNELS = {
    "indexer_scores": (
        lambda q, w, k, v: indexer_scores_kernel(q, w, k, v, interpret=False),
        [((HI, DI), jnp.bfloat16), ((HI,), jnp.bfloat16),
         ((S, DI), jnp.bfloat16), ((S,), jnp.bool_)]),
    "sparse_mla_partial": (
        lambda q, r, v: sops.partial_attend(q, r, v, D ** -0.5, RANK,
                                            interpret=False),
        [((1, 1, H, D), jnp.bfloat16), ((1, K, D), jnp.bfloat16),
         ((1, K), jnp.bool_)]),
    "gather_rows": (
        lambda c, i: gops.gather_rows(c, i, interpret=False),
        [((S, D), jnp.bfloat16), ((M,), jnp.int32)]),
    "gather_rows_dequant": (
        lambda c, s, i: gops.gather_rows_dequant(c, s, i, interpret=False),
        [((S, D), jnp.int8), ((S, 1), jnp.float16), ((M,), jnp.int32)]),
    "gather_pages": (
        lambda c, i: gops.gather_pages(c, i, PAGE, interpret=False),
        [((S, D), jnp.bfloat16), ((S // PAGE,), jnp.int32)]),
    "gather_pages_dequant": (
        lambda c, s, i: gops.gather_pages_dequant(c, s, i, PAGE,
                                                  interpret=False),
        [((S, D), jnp.int8), ((S, 1), jnp.float16),
         ((S // PAGE,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
