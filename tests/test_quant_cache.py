"""Bounds-based parity for the quantized (int8/fp8) host latent tier.

Quantization breaks bitwise parity with the bf16 tier by construction, so
these tests pin *bounds* instead: the per-element roundtrip error is
scale-limited, the logits both tiers give along one token sequence stay
within a fixed multiple of that roundtrip bound (greedy streams are not
compared: random weights leave near-tie decision margins that the
quantization noise can flip), MTP acceptance stays within 2% absolute of
the bf16 run, and the donated EngineState grows exactly the scale leaves
and nothing else.  The ESS106 jaxpr audit proves the dequant is
gather-sized in every StepProgram.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_audit as JA
from repro.configs import get_config
from repro.distributed import compression as cmp

QDTYPES = list(cmp.CACHE_QUANT_DTYPES.items())


def _cfgs():
    cfg = dataclasses.replace(get_config("deepseek-v32-exp-ess-smoke"),
                              mtp_depth=2)
    qcfg = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, host_cache_dtype="int8"))
    return cfg, qcfg


# ---------------------------------------------------------------------------
# roundtrip bounds (reference quantizer as used by the tier)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,dt", QDTYPES)
def test_roundtrip_error_is_scale_bounded(name, dt):
    x = jax.random.normal(jax.random.key(0), (6, 33, 40),
                          jnp.float32).astype(jnp.bfloat16)
    q, s = cmp.quantize_rows(x, dt)
    assert q.dtype == dt and s.dtype == cmp.SCALE_DTYPE
    assert s.shape == (6, 33, 1)
    deq = cmp.dequantize_rows(q, s, jnp.float32)
    err = np.abs(np.array(deq) - np.array(x, np.float32))
    sf = np.array(s, np.float32)
    if name == "int8":
        # |x - deq| <= scale/2 per element (round-to-nearest on the
        # stored-scale grid; the f16 scale rounding is inside the grid)
        bound = sf * 0.5 + 1e-6
    else:
        # e4m3: 3 mantissa bits -> relative error <= 2^-4 of the scaled
        # magnitude, plus the subnormal step at the bottom of the range
        bound = (np.abs(np.array(x, np.float32)) * 2.0 ** -4
                 + sf * 2.0 ** -9 + 1e-6)
    assert (err <= bound).all(), float((err - bound).max())


def test_roundtrip_bf16_rows_land_on_grid():
    # dequantizing to bf16 then re-quantizing with the *stored* scale is
    # idempotent — the quantize-once commit path relies on this grid
    x = jax.random.normal(jax.random.key(1), (4, 16), jnp.float32)
    q, s = cmp.quantize_rows(x.astype(jnp.bfloat16), jnp.int8)
    deq = cmp.dequantize_rows(q, s, jnp.float32)
    q2 = jnp.clip(jnp.round(deq / jnp.where(
        s.astype(jnp.float32) > 0, s.astype(jnp.float32), 1.0)),
        -127, 127).astype(jnp.int8)
    np.testing.assert_array_equal(np.array(q), np.array(q2))


# ---------------------------------------------------------------------------
# serve parity bounds (greedy streams + MTP acceptance)
# ---------------------------------------------------------------------------

# int8 roundtrip bound derived above: |x - deq| <= scale/2 per element,
# scale = row amax / 127, i.e. at most 1/254 of each latent row's range
INT8_ROUNDTRIP_REL = 0.5 / 127
# what that per-row error may become in the logits after 4 layers of
# attention over the quantized rows (softmax and residual amplification):
# measured 6.1x on the smoke workload; a corrupted tier (wrong scale or
# row) moves them by O(1), i.e. > 100x
LOGIT_AMPLIFICATION = 16


def _run(cfg, mtp_depth=0, max_tokens=6):
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving.api import EssEngine, SamplingParams
    params = init_params(jax.random.key(0), T.model_def(cfg))
    eng = EssEngine(params, cfg, num_slots=2, max_seq=32,
                    mtp_depth=mtp_depth)
    outs = eng.generate([10] * 4, SamplingParams(max_tokens=max_tokens),
                        max_rounds=200)
    assert all(o.finish_reason == "length" for o in outs)
    return [o.tokens for o in outs], eng.session


def _forced_logits_rel_err(cfg, qcfg, session, streams) -> float:
    """Relative L2 distance between the bf16 and int8 tiers' logits along
    one token sequence per request (its prompt + the bf16 run's stream).
    The 4-token prefill chunks read earlier chunks' rows back from the
    host tier, and the last warmup window runs ESS decode steps over the
    pool and the tier's misses, so every tier read path is exercised."""
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving import engine as E
    from repro.serving.scheduler import Request
    params = init_params(jax.random.key(0), T.model_def(cfg))
    seq = jnp.concatenate([
        jnp.concatenate([session._default_prompt(
            Request(rid=r, prompt_len=10, max_new_tokens=len(t))),
            jnp.asarray(t, jnp.int32)[None]], axis=1)
        for r, t in enumerate(streams)])
    pos = jnp.broadcast_to(jnp.arange(seq.shape[1])[None], seq.shape)
    lb, _ = E.ess_prefill(params, cfg, seq, pos, 32, prefill_chunk=4)
    lq, _ = E.ess_prefill(params, qcfg, seq, pos, 32, prefill_chunk=4)
    lb, lq = np.array(lb, np.float64), np.array(lq, np.float64)
    return float(np.linalg.norm(lq - lb) / np.linalg.norm(lb))


def test_greedy_streams_match_bf16():
    cfg, qcfg = _cfgs()
    toks_b, sess_b = _run(cfg)
    toks_q, sess_q = _run(qcfg)
    err = _forced_logits_rel_err(cfg, qcfg, sess_b, toks_b)
    assert err <= LOGIT_AMPLIFICATION * INT8_ROUNDTRIP_REL, err
    assert sess_b.report.rounds == sess_q.report.rounds
    # and the byte accounting reflects the tier dtype (42 vs 80 B/row)
    assert sess_q.report.host_bytes_per_row < sess_b.report.host_bytes_per_row


def test_mtp_acceptance_within_2pct_of_bf16():
    cfg, qcfg = _cfgs()
    toks_b, sess_b = _run(cfg, mtp_depth=2, max_tokens=8)
    toks_q, sess_q = _run(qcfg, mtp_depth=2, max_tokens=8)
    err = _forced_logits_rel_err(cfg, qcfg, sess_b, toks_b)
    assert err <= LOGIT_AMPLIFICATION * INT8_ROUNDTRIP_REL, err
    ab, aq = sess_b.report.accept_rate, sess_q.report.accept_rate
    assert sess_b.report.spec_rounds > 0
    assert abs(ab - aq) <= 0.02, (ab, aq)


def test_host_tier_rows_drift_is_scale_bounded():
    """After a real serve run the quantized tier's dequantized rows sit
    within one quantization step (plus computational drift) of the bf16
    tier's rows — the cache-level form of the bounded-logit-drift story."""
    from repro.cache import latent_cache as LC
    cfg, qcfg = _cfgs()
    _, sess_b = _run(cfg)
    _, sess_q = _run(qcfg)
    rows_b = np.array(LC.slot_latents(sess_b.caches, 0), np.float32)
    rows_q = np.array(LC.slot_latents(sess_q.caches, 0), np.float32)
    amax = np.abs(rows_b).max(axis=-1, keepdims=True)
    err = np.abs(rows_b - rows_q)
    # one int8 step is amax/127; allow 2 steps for drift accumulated
    # through the layers plus bf16 output rounding
    assert (err <= amax * (2.0 / 127.0) + 1e-5).all(), \
        float((err / np.maximum(amax, 1e-9)).max())


# ---------------------------------------------------------------------------
# donated state shape: exactly the scale leaves join
# ---------------------------------------------------------------------------

def test_engine_state_gains_only_scale_leaves():
    cfg, qcfg = _cfgs()
    for prefetch, extra in ((0, 1), (4, 2)):   # host_scales, +staged_scales
        sb = JA._abstract_state(cfg, 2, 32, prefetch)
        sq = JA._abstract_state(qcfg, 2, 32, prefetch)
        assert (len(jax.tree.leaves(sq))
                == len(jax.tree.leaves(sb)) + extra)
    # the slab-rows positional contract survives the insertion
    from repro.analysis import contracts as C
    sq = JA._abstract_state(qcfg, 2, 32, 4)
    rows = jax.tree.leaves(sq)[C.ESS105_STAGED_ROWS_LEAF]
    assert rows.dtype == jnp.int8 and rows.ndim == 4


def test_quantized_programs_donate_all_leaves():
    _, qcfg = _cfgs()
    targets = JA.build_targets(qcfg, mtp_depth=0, prefill_chunk=1)
    assert JA.audit_donation(targets=targets) == []


# ---------------------------------------------------------------------------
# ESS106: dequant is gather-sized
# ---------------------------------------------------------------------------

def test_ess106_clean_on_quantized_programs():
    _, qcfg = _cfgs()
    targets = JA.build_targets(qcfg, mtp_depth=2, prefill_chunk=2)
    assert JA.audit_tier_dequant(targets=targets) == []


def test_ess106_flags_bf16_tier_as_unquantized():
    cfg, _ = _cfgs()
    targets = JA.build_targets(cfg, mtp_depth=0, prefill_chunk=1)
    fs = JA.audit_tier_dequant(targets=targets)
    assert fs and all(f.rule == "ESS106" for f in fs)
    assert "no quantized state leaf" in fs[0].message


def test_ess106_checker_flags_tier_sized_dequant():
    fs = JA.check_tier_dequants("decode", [(4096, "int8", "bfloat16")],
                                threshold=4096)
    assert [f.rule for f in fs] == ["ESS106"]
    assert "4096" in fs[0].message and fs[0].scope == "decode"
    assert JA.check_tier_dequants("decode", [], 4096) == []


def test_find_big_dequants_on_synthetic_jaxpr():
    big = jax.ShapeDtypeStruct((64, 64), jnp.int8)

    def widen(q):
        return q.astype(jnp.bfloat16) * 2.0

    jaxpr = jax.make_jaxpr(widen)(big)
    assert JA.find_big_dequants(jaxpr, 64 * 64) \
        == [(64 * 64, "int8", "bfloat16")]
    assert JA.find_big_dequants(jaxpr, 64 * 64 + 1) == []

    def stays_narrow(q):
        return q + jnp.int8(1)

    assert JA.find_big_dequants(
        jax.make_jaxpr(stays_narrow)(big), 1) == []


# ---------------------------------------------------------------------------
# byte-denominated admission (dtype-aware, not raw page counts)
# ---------------------------------------------------------------------------

def test_byte_budget_floors_pages_by_storage_dtype():
    from repro.cache import latent_cache as LC
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving.engine import ServeSession
    cfg, qcfg = _cfgs()
    params = init_params(jax.random.key(0), T.model_def(cfg))
    budget = 4 * LC.host_page_bytes(qcfg, qcfg.param_dtype)
    sb = ServeSession(params, cfg, num_slots=2, max_seq=32,
                      host_byte_budget=budget)
    sq = ServeSession(params, qcfg, num_slots=2, max_seq=32,
                      host_byte_budget=budget)
    assert sb.num_pages == budget // LC.host_page_bytes(cfg, cfg.param_dtype)
    assert sq.num_pages == 4
    assert sq.num_pages >= 2 * sb.num_pages
    # same byte budget -> same byte ceiling, whatever the dtype
    assert (sq.num_pages * sq.host_page_bytes <= budget
            and sb.num_pages * sb.host_page_bytes <= budget)


def test_admission_blocks_on_bytes_not_pages():
    from repro.cache import latent_cache as LC
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving.engine import ServeSession
    from repro.serving.scheduler import Request
    cfg, qcfg = _cfgs()
    params = init_params(jax.random.key(0), T.model_def(qcfg))
    budget = 2 * LC.host_page_bytes(qcfg, qcfg.param_dtype)
    # a third slot is free, so the *byte* gate is what must block rid=2
    s = ServeSession(params, qcfg, num_slots=3, max_seq=32,
                     host_byte_budget=budget)
    s.submit(Request(rid=0, prompt_len=6, max_new_tokens=4))   # 1 page
    s.submit(Request(rid=1, prompt_len=6, max_new_tokens=4))   # 1 page
    s.submit(Request(rid=2, prompt_len=6, max_new_tokens=4))   # blocked
    s.step_round()
    assert len(s.sched.running) == 2
    assert any("host bytes" in e for e in s.report.events)
    s.run(max_rounds=100)           # frees pages; rid=2 completes too
    assert not s.sched.running and not s.sched.queue
