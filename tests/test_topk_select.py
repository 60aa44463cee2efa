"""``topk_ids`` selects without a sort; it must return exactly the ids of
``lax.top_k`` over the masked scores, in the same order (the pool's miss
ranks and overflow drops follow that order)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import mla as M


def _case(name):
    """(scores [..., S], valid [..., S] or None, k)."""
    rng = np.random.default_rng(CASES.index(name))
    if name == "decode":             # [B,1,S], each slot its own length
        S, k = 40960, 2048
        x = rng.standard_normal((4, 1, S)).astype(np.float32)
        lens = rng.integers(k, S + 1, size=(4, 1))
        return x, np.arange(S) < lens[..., None], k
    if name == "prefill":            # [1,C,S], causal within the chunk
        S, k, C, start = 1280, 256, 32, 400
        x = np.maximum(rng.standard_normal((1, C, S)), 0).astype(np.float32)
        return x, np.arange(S) <= start + np.arange(C)[None, :, None], k
    if name == "spec":               # [B,Q>1,S], per-query lengths
        S, k = 640, 64
        x = rng.standard_normal((3, 4, S)).astype(np.float32)
        lens = 300 + np.arange(4)[None, :] + 17 * np.arange(3)[:, None]
        return x, np.arange(S) < lens[..., None], k
    if name == "ties":               # rounded scores: many exact ties
        x = np.round(rng.standard_normal((5, 1000)) * 2) / 2
        return x.astype(np.float32), None, 300
    if name == "signed_zeros":       # -0.0 orders below +0.0
        x = np.where(rng.random((4, 512)) < 0.5, -0.0, 0.0)
        x[:, ::7] = rng.standard_normal((4, 74))
        return x.astype(np.float32), None, 200
    if name == "short_rows":         # fewer valid than k: NEG_INF filler
        S, k = 700, 256
        x = rng.standard_normal((4, 1, S)).astype(np.float32)
        lens = np.array([0, 1, 100, 255])[:, None]
        return x, np.arange(S) < lens[..., None], k
    if name == "k_equals_s":
        x = rng.standard_normal((3, 2, 384)).astype(np.float32)
        return x, None, 384
    if name == "all_equal":
        return np.full((2, 1, 520), 0.25, np.float32), None, 130
    raise KeyError(name)


CASES = ["decode", "prefill", "spec", "ties", "signed_zeros", "short_rows",
         "k_equals_s", "all_equal"]


@pytest.mark.parametrize("name", CASES)
def test_topk_ids_matches_lax_top_k(name):
    x, valid, k = _case(name)
    masked = x if valid is None else np.where(valid, x, M.NEG_INF)
    want = jax.jit(lambda s: jax.lax.top_k(s, k)[1])(masked)
    got = jax.jit(lambda s, v: M.topk_ids(s, k, v))(x, valid)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", ["decode", "prefill", "spec", "short_rows"])
def test_req_valid_from_prefix_length(name):
    """``ids < len`` gives what a ``take_along_axis`` over the prefix mask
    gave: the mask ``arange(S) < len`` is a prefix of every row."""
    x, valid, k = _case(name)
    lens = valid.sum(-1)
    assert np.array_equal(valid, np.arange(x.shape[-1]) < lens[..., None])
    ids = M.topk_ids(jnp.asarray(x), k, valid)
    old = jnp.take_along_axis(
        jnp.broadcast_to(valid, x.shape), ids, axis=-1)
    np.testing.assert_array_equal(np.asarray(ids < lens[..., None]),
                                  np.asarray(old))


def test_round_programs_sort_only_k_wide():
    """No round program sorts a row of indexer scores: every sort under
    ``ess.topk`` orders the k selected ids."""
    from repro.analysis import hlo_scopes as H
    from repro.analysis import jaxpr_audit as JA

    cfg = JA._smoke_cfg()
    max_seq = 96
    K = cfg.dsa.index_topk
    assert K < max_seq
    seen = 0
    for t in JA.build_targets(cfg, max_seq=max_seq, mtp_depth=1,
                              prefill_chunk=2):
        text = t.fn.lower(*t.args).compile().as_text()
        _, scopes = H.op_scopes(text)
        for line in text.splitlines():
            m = re.match(r"\s*(?:ROOT )?%?(\S+) = \(?\w+\[([\d,]+)\].* sort\("
                         r".*dimensions=\{(\d+)\}", line)
            if m and scopes[m.group(1)] == "ess.topk":
                seen += 1
                width = int(m.group(2).split(",")[int(m.group(3))])
                assert width == K, (t.kind, line)
    assert seen > 0
