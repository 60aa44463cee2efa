"""ESS core behaviour: overlap exactness, warmup, locality metric, engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import lru_pool as LP
from repro.core import overlap as OV
from repro.core import warmup as WU
from repro.core.similarity import intra_layer_similarity, similarity_trace
from repro.models import mla as M
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving import engine as E


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-v32-exp-ess-smoke")
    mla_p = init_params(jax.random.key(0), M.mla_def(cfg))
    idx_p = init_params(jax.random.key(1), M.indexer_def(cfg))
    B, S, ctx = 3, 64, 40
    lat = jax.random.normal(jax.random.key(2), (B, S, cfg.mla.latent_dim),
                            jnp.float32) * 0.5
    ikeys = jax.random.normal(jax.random.key(3), (B, S, cfg.dsa.index_dim),
                              jnp.float32)
    lens = jnp.full((B,), ctx, jnp.int32)
    x = jax.random.normal(jax.random.key(4), (B, 1, cfg.d_model),
                          jnp.float32) * 0.3
    pos = jnp.full((B, 1), ctx - 1, jnp.int32)
    return cfg, mla_p, idx_p, B, S, lat, ikeys, lens, x, pos


@pytest.mark.parametrize("mode", ["none", "da", "dba"])
def test_overlap_modes_exact_vs_monolithic(setup, mode):
    cfg, mla_p, idx_p, B, S, lat, ikeys, lens, x, pos = setup
    ref, _ = M.sparse_mla_decode(mla_p, idx_p, cfg, x, pos, lat, ikeys, lens)
    cfg_x = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))
    P = max(int(0.5 * S), cfg.dsa.index_topk)
    pool = LP.init_pool(B, P, S, cfg.mla.latent_dim, jnp.float32)
    st = OV.ESSLayerState(pool, lat)
    out, st2, stats = OV.ess_sparse_attention(
        mla_p, idx_p, cfg_x, x, pos, st, ikeys, lens, overlap=mode)
    np.testing.assert_allclose(np.array(out), np.array(ref), atol=1e-5)
    assert int(np.array(stats.misses).sum()) > 0        # cold pool missed


def test_pool_reuse_reduces_misses(setup):
    cfg, mla_p, idx_p, B, S, lat, ikeys, lens, x, pos = setup
    cfg_x = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))
    P = max(int(0.5 * S), cfg.dsa.index_topk)
    pool = LP.init_pool(B, P, S, cfg.mla.latent_dim, jnp.float32)
    st = OV.ESSLayerState(pool, lat)
    _, st1, s1 = OV.ess_sparse_attention(mla_p, idx_p, cfg_x, x, pos, st,
                                         ikeys, lens, overlap="da")
    _, _, s2 = OV.ess_sparse_attention(mla_p, idx_p, cfg_x, x, pos, st1,
                                       ikeys, lens, overlap="da")
    assert int(np.array(s2.misses).sum()) < int(np.array(s1.misses).sum())
    assert int(np.array(s2.misses).sum()) == 0          # same query -> hits


def test_lru_warmup_preheats_pool(setup):
    cfg, mla_p, idx_p, B, S, lat, ikeys, lens, x, pos = setup
    P = max(int(0.5 * S), cfg.dsa.index_topk)
    pool0 = LP.init_pool(B, P, S, cfg.mla.latent_dim, jnp.float32)
    x_tail = jnp.repeat(x, 4, axis=1)
    pool_w = WU.lru_warmup(pool0, lat, x_tail, idx_p, ikeys, lens, cfg,
                           slot_mask=None)
    cfg_x = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))
    _, _, s_cold = OV.ess_sparse_attention(
        mla_p, idx_p, cfg_x, x, pos, OV.ESSLayerState(pool0, lat), ikeys,
        lens, overlap="da")
    _, _, s_warm = OV.ess_sparse_attention(
        mla_p, idx_p, cfg_x, x, pos, OV.ESSLayerState(pool_w, lat), ikeys,
        lens, overlap="da")
    assert int(np.array(s_warm.misses).sum()) < \
        int(np.array(s_cold.misses).sum())


def test_engine_prefill_decode_matches_monolithic():
    cfg = get_config("deepseek-v32-exp-ess-smoke")
    params = init_params(jax.random.key(0), T.model_def(cfg))
    B, S, Smax = 2, 24, 40
    toks = jax.random.randint(jax.random.key(1), (B, S + 1), 0,
                              cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S + 1)[None], (B, S + 1))
    # monolithic reference
    pf = T.forward(params, cfg, toks[:, :S], pos[:, :S], mode="prefill")
    cm = pf.caches
    cm["mla"] = jax.tree.map(
        lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, Smax - S), (0, 0))),
        cm["mla"])
    dm = T.forward(params, cfg, toks[:, S:S + 1], pos[:, S:S + 1],
                   mode="decode", caches=cm)
    # ESS path (exact envelope, cold pool)
    cfg_x = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))
    _, ce = E.ess_prefill(params, cfg_x, toks[:, :S], pos[:, :S], Smax,
                          do_warmup=False)
    oe = E.ess_decode(params, cfg_x, toks[:, S:S + 1], pos[:, S:S + 1], ce)
    # fp reassociation (gather-K vs masked-dense softmax) can flip Top-K
    # selection at near-tie scores in a handful of positions; the bulk of
    # the logits must agree tightly
    diff = np.abs(np.array(oe.logits[:, -1]) - np.array(dm.logits[:, -1]))
    assert diff.max() < 5e-2
    assert diff.mean() < 5e-3          # bulk within bf16 rounding scale


def test_engine_prefill_chunked_matches_train():
    cfg = get_config("deepseek-v32-exp-ess-smoke")
    params = init_params(jax.random.key(0), T.model_def(cfg))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ref = T.forward(params, cfg, toks, pos, mode="train").logits
    lg, _ = E.ess_prefill(params, cfg, toks, pos, 40, do_warmup=False)
    # same Top-K selection semantics; a few near-tie positions may flip
    # under fp reassociation (chunk-gather vs dense-masked attention)
    diff = np.abs(np.array(lg) - np.array(ref))
    assert diff.max() < 5e-2
    assert diff.mean() < 2e-3
    # chunked prefill streams through the same engine: bit-identical
    # hidden states; the unembed dot's f32 accumulation order follows
    # its row count (XLA CPU), so the logits agree to the last few ulps
    lg7, _ = E.ess_prefill(params, cfg, toks, pos, 40, do_warmup=False,
                           prefill_chunk=7)
    lg = np.array(lg)
    np.testing.assert_allclose(
        np.array(lg7), lg, rtol=0,
        atol=4 * np.finfo(np.float32).eps * np.abs(lg).max())


def test_intra_layer_similarity_eq1():
    a = jnp.array([[1, 2, 3, 4]])
    b = jnp.array([[3, 4, 5, 6]])
    r = intra_layer_similarity(a, b)
    np.testing.assert_allclose(np.array(r), [0.5])
    # identical sets -> 1, disjoint -> 0
    np.testing.assert_allclose(np.array(intra_layer_similarity(a, a)), [1.0])
    c = jnp.array([[7, 8, 9, 10]])
    np.testing.assert_allclose(np.array(intra_layer_similarity(a, c)), [0.0])
    tr = similarity_trace(jnp.stack([a, b, c]))
    assert tr.shape == (2, 1)


def test_dba_equals_da_results(setup):
    """DBA is a scheduling change only — numerics must match DA."""
    cfg, mla_p, idx_p, B, S, lat, ikeys, lens, x, pos = setup
    cfg_x = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, max_miss_ratio=1.0))
    P = max(int(0.5 * S), cfg.dsa.index_topk)
    pool = LP.init_pool(B, P, S, cfg.mla.latent_dim, jnp.float32)
    st = OV.ESSLayerState(pool, lat)
    out_da, _, _ = OV.ess_sparse_attention(mla_p, idx_p, cfg_x, x, pos, st,
                                           ikeys, lens, overlap="da")
    out_dba, _, _ = OV.ess_sparse_attention(mla_p, idx_p, cfg_x, x, pos, st,
                                            ikeys, lens, overlap="dba")
    np.testing.assert_allclose(np.array(out_da), np.array(out_dba),
                               atol=1e-5)
