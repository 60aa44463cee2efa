"""A whole run of the harness on the CPU at a tiny size, with the chip
check skipped: the reference agrees with the program's own dense forward,
a sound run comes out correct, and each fault planted in the timed path,
and the float8 control put in its place, come out not correct."""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import config_map
import reference
import run
import tiny
import weights

SEED = 2 ** 33 + 7


def _run(cell, seconds=0.5, **kw):
    tmp = tempfile.mkdtemp()
    bench = tiny.make_tree(tmp)
    return run.run_cell(bench, cell, SEED, seconds, False,
                        require_tpu=False, root=tmp, data=tmp, **kw)


@pytest.fixture
def fresh_programs():
    from repro.serving import step
    step.get_programs.cache_clear()
    yield
    step.get_programs.cache_clear()


@pytest.mark.parametrize("topk", [8, 64])
def test_reference_matches_the_program_dense_forward(topk):
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    spec = dict(tiny.CONFIG, index_topk=topk)
    cfg = config_map.to_program(spec).cfg
    params = weights.make(abstract_params(T.model_def(cfg)), 5,
                          config_map.arch_module(spec))
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        c32 = dataclasses.replace(cfg, param_dtype=jnp.float32)
        want = T.forward(p32, c32, jnp.asarray(toks)[None],
                         jnp.arange(40)[None], mode="train").logits[0]
        ref = reference.Reference(spec, params, 64, block=16)
        h = ref.hidden(toks, 0)
        got = jnp.einsum("qd,vd->qv",
                         ref._norm(params["final_norm"], h),
                         params["unembed"].astype(jnp.float32))
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_sound_run_is_correct_and_control_is_not(fresh_programs):
    out = _run("tiny.closed")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 3 and out["failed"] == 0
    assert out["metrics"]["decode_tok_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    ctl = _run("tiny.closed", control=True)
    assert not ctl["correct"], ctl["checks"]
    assert list(ctl["checks"]) == ["served_gap"]
    assert ctl["checks"]["served_gap"]["limit"] == \
        out["checks"]["served_gap"]["limit"]


def test_altered_token_is_caught(fresh_programs, monkeypatch):
    from repro.serving import step
    real = step.greedy
    monkeypatch.setattr(step, "greedy",
                        lambda lg: (real(lg) + 1) % lg.shape[-1])
    out = _run("tiny.closed")
    assert not out["correct"], out["checks"]


def test_step_returning_its_state_unchanged_is_caught(fresh_programs,
                                                      monkeypatch):
    from repro.serving import engine
    real = engine.ess_decode

    def frozen(params, cfg, tokens, positions, caches, **kw):
        out = real(params, cfg, tokens, positions, caches, **kw)
        return engine.DecodeOut(out.logits, caches, out.stats)

    monkeypatch.setattr(engine, "ess_decode", frozen)
    out = _run("tiny.closed")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("trial", range(6))
def test_topk_select_equals_lax_top_k(trial):
    rng = np.random.default_rng(trial)
    q, S, k = 16, 300, int(rng.integers(1, 120))
    sc = rng.normal(size=(q, S)).astype(np.float32)
    if trial % 2 == 0:
        sc = np.round(sc * 2) / 2                       # many exact ties
    valid = np.arange(S)[None] < rng.integers(1, S, q)[:, None]
    ids, ok = reference.topk_select(jnp.asarray(sc), jnp.asarray(valid), k)
    _, want = jax.lax.top_k(jnp.where(valid, sc, -3e38), k)
    want_ok = np.take_along_axis(valid, np.asarray(want), 1)
    for r in range(q):
        assert set(np.asarray(ids)[r][np.asarray(ok)[r]].tolist()) == \
            set(np.asarray(want)[r][want_ok[r]].tolist())
