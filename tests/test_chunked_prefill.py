"""Chunked decode-interleaved prefill + inactive-slot decode gating.

Covers this PR's tentpole and headline bugfix:

* chunked prefill (any ``prefill_chunk``) is **bit-identical** to the
  one-shot ``ess_prefill`` path — host latents, indexer keys, first
  sampled token;
* a long prompt admits without stalling the decode batch (decode rounds
  continue between prefill chunks);
* masked (freed / mid-prefill) slots are gated *inside* ``ess_decode``:
  no phantom host-page writes, no pool pollution, no lens drift;
* preemption resets per-attempt progress so a re-admitted request
  generates its full ``max_new_tokens``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import latent_cache as LC
from repro.configs import get_config
from repro.configs.base import DSAConfig
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving import engine as E
from repro.serving.sampling import greedy
from repro.serving.scheduler import Request


def smoke_cfg(**ess_overrides):
    cfg = get_config("deepseek-v32-exp-ess-smoke")
    if ess_overrides:
        cfg = dataclasses.replace(
            cfg, ess=dataclasses.replace(cfg.ess, **ess_overrides))
    return cfg


# ---------------------------------------------------------------------------
# Parity: chunked == one-shot, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [7, 64])
def test_chunked_prefill_bitwise_parity(chunk):
    """Host latents, indexer keys and the first sampled token must be
    bit-identical between chunked and one-shot prefill: every chunk stage
    (score, top-k, gather, attend, ffn) is fixed-shape and per-token.
    The logits are held to a few-ulp bound (see below)."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    B, S, Smax = 2, 24, 64
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    lg1, c1 = E.ess_prefill(params, cfg, toks, pos, Smax, do_warmup=False)
    lgc, cc = E.ess_prefill(params, cfg, toks, pos, Smax, do_warmup=False,
                            prefill_chunk=chunk)
    np.testing.assert_array_equal(np.array(c1.host_latent),
                                  np.array(cc.host_latent))
    for l in range(cfg.num_layers):
        np.testing.assert_array_equal(np.array(c1.ikeys[l]),
                                      np.array(cc.ikeys[l]))
    np.testing.assert_array_equal(np.array(c1.lens), np.array(cc.lens))
    np.testing.assert_array_equal(np.array(greedy(lg1[:, -1])),
                                  np.array(greedy(lgc[:, -1])))
    # full prefill logits: the final hidden states are bit-identical, but
    # the f32-accumulated unembed dot runs over B*chunk rows instead of
    # B*S, and XLA's CPU dot picks its accumulation order by shape — so
    # the logits may differ in the last f32 ulp (reduction order, not
    # math; measured at chunk 7: 1.55 ulps of the largest logit)
    lg1, lgc = np.array(lg1), np.array(lgc)
    np.testing.assert_allclose(
        lgc, lg1, rtol=0,
        atol=4 * np.finfo(np.float32).eps * np.abs(lg1).max())


def test_serve_session_chunked_prefill_matches_oneshot_first_token():
    """The serve loop's in-place chunked prefill (scatter into mapped
    pages, no donor/graft) reproduces the compat path's host rows and
    first token, bit for bit.

    ``do_warmup=True`` routes the session through the legacy op-by-op
    chunk path — the only execution substrate comparable bit-level
    against the eager one-shot reference (XLA's full-graph fusion
    perturbs low-order float bits, so jitted StepProgram chunks are
    held to *stream*-level parity instead — tests/test_compiled_serve).
    The warmup replay touches only the pools, never the host rows or
    the first token compared here."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    PROMPT, SMAX = 20, 48

    def prompt_fn(req):
        return jax.random.randint(jax.random.key(1000 + req.rid),
                                  (1, req.prompt_len), 0, cfg.vocab_size)

    session = E.ServeSession(params, cfg, num_slots=2, max_seq=SMAX,
                             prefill_chunk=7, prompt_fn=prompt_fn,
                             do_warmup=True)
    req = Request(rid=0, prompt_len=PROMPT, max_new_tokens=4)
    session.submit(req)
    session.admit()
    while session._prefill:
        session.prefill_round()
    # reference: one-shot donor prefill of the same prompt
    toks = prompt_fn(req)
    pos = jnp.arange(PROMPT, dtype=jnp.int32)[None]
    lg, donor = E.ess_prefill(params, cfg, toks, pos, SMAX, do_warmup=False)
    assert int(session.tok[0]) == int(greedy(lg[:, -1])[0])
    got = LC.slot_latents(session.caches, 0)[:, :PROMPT]
    ref = LC.slot_latents(donor, 0)[:, :PROMPT]
    np.testing.assert_array_equal(np.array(got), np.array(ref))


# ---------------------------------------------------------------------------
# Long-prompt admission: decode keeps running between chunks
# ---------------------------------------------------------------------------

def test_32k_prompt_admits_without_decode_stall():
    """A 32K-token prompt streams through chunked prefill while the other
    slot keeps decoding — the one-shot donor path would freeze the batch
    for the whole prefill."""
    base = smoke_cfg()
    cfg = dataclasses.replace(                    # nano variant: 2 layers,
        base, num_layers=2,                       # 1-head indexer, CPU-sized
        dsa=DSAConfig(index_heads=1, index_dim=8, index_topk=8))
    params = init_params(jax.random.key(0), T.model_def(cfg))
    LONG, SHORT = 32768, 8
    session = E.ServeSession(params, cfg, num_slots=2, max_seq=LONG + 8,
                             prefill_chunk=4096)
    reqs = [Request(rid=0, prompt_len=SHORT, max_new_tokens=24),
            Request(rid=1, prompt_len=LONG, max_new_tokens=2)]
    decode_during_prefill = []

    def on_round(s, rnd):
        if s._prefill:                            # rid=1 still prefilling
            decode_during_prefill.append(s.report.decode_tokens)

    report = session.run(reqs, max_rounds=64, on_round=on_round)
    assert sorted(report.finished_rids) == [0, 1]
    assert report.prefill_chunks >= LONG // 4096 + 1
    assert report.prefill_tokens == LONG + SHORT
    # decode rounds continued between rid=1's chunks
    assert decode_during_prefill and \
        decode_during_prefill[-1] > decode_during_prefill[0]
    chunk_evs = [e for e in report.events if "prefill chunk" in e]
    assert len(chunk_evs) == report.prefill_chunks
    assert report.ttft_rounds[1] >= LONG // 4096  # one chunk per round


# ---------------------------------------------------------------------------
# Headline bugfix: inactive slots are masked inside the decode step
# ---------------------------------------------------------------------------

def test_masked_decode_writes_nothing():
    """With every slot masked, a decode step must leave host pages, pools
    and lens bit-identical — freed slots can no longer run phantom steps
    that scatter garbage latents or admit zeros into their pool."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    B, S, Smax = 2, 12, 32
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    _, caches = E.ess_prefill(params, cfg, toks, pos, Smax, do_warmup=False)
    nxt = jax.random.randint(jax.random.key(2), (B, 1), 0, cfg.vocab_size)

    mask = jnp.zeros((B,), bool)
    out = E.ess_decode(params, cfg, nxt, caches.lens[:, None], caches,
                       slot_mask=mask)
    np.testing.assert_array_equal(np.array(out.caches.host_latent),
                                  np.array(caches.host_latent))
    np.testing.assert_array_equal(np.array(out.caches.lens),
                                  np.array(caches.lens))
    for p0, p1 in zip(caches.pools, out.caches.pools):
        np.testing.assert_array_equal(np.array(p0.ids), np.array(p1.ids))
        np.testing.assert_array_equal(np.array(p0.data), np.array(p1.data))
    for l in range(cfg.num_layers):
        np.testing.assert_array_equal(np.array(out.caches.ikeys[l]),
                                      np.array(caches.ikeys[l]))
    assert int(np.array(out.stats["hits"]).sum()) == 0
    assert int(np.array(out.stats["misses"]).sum()) == 0


def test_freed_slot_does_not_alias_live_slot_pages():
    """Regression for the serve-loop aliasing bug: a freed slot whose
    stale block table still points at (now someone else's) pages used to
    scatter a garbage latent row through it.  Decode with slot 1 freed:
    slot 0's host pages change only at its own append row, and slot 1's
    pool stays empty."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    B, S, Smax = 2, 12, 32
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    _, caches = E.ess_prefill(params, cfg, toks, pos, Smax, do_warmup=False)
    # free slot 1 the buggy way: lens zeroed, block table STALE — and make
    # the staleness adversarial: slot 1's table aliases slot 0's pages
    caches = LC.reset_slot(caches, 1)
    caches = caches._replace(
        block_tables=caches.block_tables.at[1].set(caches.block_tables[0]))
    before = np.array(caches.host_latent)

    nxt = jax.random.randint(jax.random.key(2), (B, 1), 0, cfg.vocab_size)
    mask = jnp.asarray([True, False])
    out = E.ess_decode(params, cfg, nxt, caches.lens[:, None], caches,
                       slot_mask=mask)
    after = np.array(out.caches.host_latent)

    # slot 0 appended exactly one row per layer at position S -> page
    # S // R, row S % R; every other host row is bit-identical.  The old
    # phantom step wrote slot 1's garbage at position 0 == slot 0's page 0.
    R = cfg.ess.host_page_rows
    bt0 = np.array(caches.block_tables[0])
    pg, rw = bt0[S // R], S % R
    changed = (after != before).any(axis=-1)          # [L, NP, R]
    expect = np.zeros_like(changed)
    expect[:, pg, rw] = True
    np.testing.assert_array_equal(changed, changed & expect)
    assert changed[:, pg, rw].all()                   # the append happened
    # freed slot's pool stayed empty (no phantom admit of a zero row)
    for p in out.caches.pools:
        assert (np.array(p.ids[1]) == -1).all()
    assert int(np.array(out.caches.lens[1])) == 0

    # the same step WITHOUT the mask exhibits the bug this PR fixes: the
    # phantom write lands in slot 0's page 0 (kept as documentation that
    # this regression test bites)
    out_buggy = E.ess_decode(params, cfg, nxt, caches.lens[:, None], caches)
    after_buggy = np.array(out_buggy.caches.host_latent)
    assert (after_buggy[:, bt0[0], 0] != before[:, bt0[0], 0]).any()


def test_serve_loop_freed_slot_rounds_leave_it_untouched():
    """Drive the real serve loop to a state with one freed slot and keep
    decoding: the freed slot's lens/pool stay clean with no post-hoc
    fixups (the old loop re-zeroed lens after every phantom step)."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    session = E.ServeSession(params, cfg, num_slots=2, max_seq=48)
    reqs = [Request(rid=0, prompt_len=12, max_new_tokens=20),
            Request(rid=1, prompt_len=12, max_new_tokens=2)]
    report = None
    for r in reqs:
        session.submit(r)
    for _ in range(8):                # rid=1 finishes, slot 1 frees
        session.step()
    assert not session.sched.slots[1].active
    for _ in range(4):                # decode rounds with a freed slot
        session.step()
    assert int(session.caches.lens[1]) == 0
    for p in session.caches.pools:
        assert (np.array(p.ids[1]) == -1).all()
    assert (np.array(session.caches.block_tables[1]) == -1).all()


def test_serve_warmup_replays_after_last_chunk():
    """With ``do_warmup=True`` the slot's Sparse Memory Pool is preheated
    (LRU-Warmup replay from its mapped pages) after the final prefill
    chunk, before the first decode step — and the warmed entries match the
    host tier."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    session = E.ServeSession(params, cfg, num_slots=2, max_seq=48,
                             do_warmup=True, prefill_chunk=16)
    session.submit(Request(rid=0, prompt_len=20, max_new_tokens=4))
    session.admit()
    while session._prefill:
        session.prefill_round()
    host = LC.slot_latents(session.caches, 0)
    n_warm = 0
    for layer, p in enumerate(session.caches.pools):
        ids = np.array(p.ids[0])
        for j, pid in enumerate(ids):
            if pid >= 0:
                n_warm += 1
                np.testing.assert_array_equal(
                    np.array(p.data[0, j]), np.array(host[layer, pid]))
        # the un-admitted slot stays cold
        assert (np.array(p.ids[1]) == -1).all()
    assert n_warm > 0
    # warmed pool reduces first-decode misses vs a cold session
    cold = E.ServeSession(params, cfg, num_slots=2, max_seq=48,
                          do_warmup=False, prefill_chunk=16)
    cold.submit(Request(rid=0, prompt_len=20, max_new_tokens=4))
    cold.admit()
    while cold._prefill:
        cold.prefill_round()
    mask = jnp.asarray([True, False])
    o_warm = E.ess_decode(params, cfg, session.tok[:, None],
                          session.caches.lens[:, None], session.caches,
                          slot_mask=mask)
    o_cold = E.ess_decode(params, cfg, cold.tok[:, None],
                          cold.caches.lens[:, None], cold.caches,
                          slot_mask=mask)
    assert int(np.array(o_warm.stats["misses"]).sum()) < \
        int(np.array(o_cold.stats["misses"]).sum())


def test_serve_warmup_depth_independent_of_chunking():
    """Warmup windows span chunk boundaries: prompt_len=17 with
    prefill_chunk=16 leaves a 1-token final chunk, but the replay must
    still cover the full ``warmup_windows`` tail (accumulated across
    chunks) — bit-identical pool state vs a single-chunk prefill."""
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))

    def mk(chunk):
        s = E.ServeSession(params, cfg, num_slots=1, max_seq=32,
                           do_warmup=True, prefill_chunk=chunk)
        s.submit(Request(rid=0, prompt_len=17, max_new_tokens=2))
        s.admit()
        while s._prefill:
            s.prefill_round()
        return s

    a, b = mk(16), mk(64)
    for pa, pb in zip(a.caches.pools, b.caches.pools):
        np.testing.assert_array_equal(np.array(pa.ids), np.array(pb.ids))
        np.testing.assert_array_equal(np.array(pa.data), np.array(pb.data))
        np.testing.assert_array_equal(np.array(pa.last_use),
                                      np.array(pb.last_use))
    assert any((np.array(p.ids[0]) >= 0).sum() > 0 for p in a.caches.pools)
    assert int(a.tok[0]) == int(b.tok[0])


# ---------------------------------------------------------------------------
# Preemption resets per-attempt progress
# ---------------------------------------------------------------------------

def test_preempt_resets_generated_and_readmit_serves_full_budget():
    cfg = smoke_cfg()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    NEW = 6
    session = E.ServeSession(params, cfg, num_slots=1, max_seq=48)
    req = Request(rid=0, prompt_len=12, max_new_tokens=NEW)
    session.submit(req)
    session.step()                     # admit + prefill + 1 decode token
    session.step()
    assert req.generated == 2
    session.preempt(0)
    assert req.generated == 0          # per-attempt progress reset
    assert req.preempted_count == 1

    # re-admission: the attempt re-prefills and must produce the FULL
    # max_new_tokens again (the old code finished `generated` early).
    # The prefill first token consumes one budget unit, so the decode
    # phase delivers (and rounds through) NEW - 1 tokens and the stream
    # holds NEW total.
    decode_rounds_before = session.report.rounds
    report = session.run(max_rounds=40)
    assert report.finished_rids == [0]
    assert req.generated == NEW - 1
    assert len(session.outputs[0]) == NEW == req.generated + 1
    assert report.rounds - decode_rounds_before == NEW - 1
