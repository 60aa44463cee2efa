"""Stage names of the serve round in the profiler trace: each round kind
compiles to its own XLA module name, every heavy op of the optimized
decode program lies under an ``ess.*`` device scope, and a traced round
holds ``ess.round`` with its host stages in the order they run."""

import dataclasses
import glob
import os
import re

import jax
import pytest

from repro.analysis import contracts as C
from repro.analysis import hlo_scopes as H
from repro.analysis import jaxpr_audit as JA
from repro.configs import get_config
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving import engine as E
from repro.serving.scheduler import Request

HEAVY = ("dot", "sort", "gather", "scatter")


def test_round_programs_have_distinct_module_names():
    targets = JA.build_targets(JA._smoke_cfg(), mtp_depth=2,
                               prefill_chunk=2)
    names = {}
    for t in targets:
        m = re.match(r"module @(\S+)", t.fn.lower(*t.args).as_text())
        names.setdefault(t.kind.split("/")[0], set()).add(m.group(1))
    assert names == {k: {v} for k, v in C.ROUND_MODULES.items()}


@pytest.mark.parametrize("host_dtype,prefetch", [("bf16", 0), ("int8", 4)])
def test_decode_heavy_ops_lie_under_a_stage_scope(host_dtype, prefetch):
    cfg = JA._smoke_cfg(paged=True, host_dtype=host_dtype)
    t = next(t for t in JA.build_targets(cfg, mtp_depth=0, prefill_chunk=1,
                                         prefetch=prefetch)
             if t.kind == "decode")
    text = t.fn.lower(*t.args).compile().as_text()
    module, scopes = H.op_scopes(text)
    _, instrs = H.parse(text)
    assert module == C.ROUND_MODULES["decode"]
    entry = next(r["computation"] for r in instrs.values()
                 if r["computation"].startswith("main"))
    heavy = {n for n, r in instrs.items()
             if r["opcode"] in HEAVY
             or (r["computation"] == entry
                 and r["opcode"] in ("fusion", "custom-call"))}
    assert len(heavy) > 50
    stray = sorted((n, instrs[n]["op_name"]) for n in heavy
                   if scopes[n] not in C.DEVICE_SCOPES)
    assert not stray
    used = {scopes[n] for n in heavy}
    want = set(C.DEVICE_SCOPES) - ({"ess.prefetch"} if not prefetch
                                   else set())
    assert used == want


def test_op_scope_rules():
    """Innermost scope wins; an instruction without metadata takes its
    consumer's scope; an op_name with no ess scope stays unscoped."""
    import jax.numpy as jnp

    def decode_round(q, r):
        with jax.named_scope("ess.attend"):
            with jax.named_scope("ess.pool"):
                r = r * 2.0
            # a dot over a size-1 batch dim: XLA rebuilds it without
            # metadata
            s = jnp.einsum("bqhd,bqkd->bqhk", q, r) * 0.5
        return jnp.sort(s, axis=-1)

    text = jax.jit(decode_round).lower(
        jnp.ones((2, 1, 4, 32)), jnp.ones((2, 1, 8, 32))).compile().as_text()
    module, scopes = H.op_scopes(text)
    _, instrs = H.parse(text)
    assert module == "jit_decode_round"
    by_op = {}
    for n, r in instrs.items():
        by_op.setdefault(r["opcode"], set()).add(scopes[n])
    assert by_op["dot"] == {"ess.attend"}
    assert by_op["sort"] == {H.UNSCOPED}
    assert H.innermost_scope("jit(f)/ess.attend/ess.pool/mul") == "ess.pool"
    assert H.innermost_scope("jit(f)/jit(raw)") is None
    assert H.innermost_scope("jit(f)/add") == H.UNSCOPED


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith("ess."):
                    spans.append((e.start_ns, e.end_ns, name,
                                  dict(e.stats)))
    return sorted(spans)


def test_traced_round_holds_its_stage_spans_in_order(tmp_path):
    cfg = dataclasses.replace(get_config("deepseek-v32-exp-ess-smoke"),
                              mtp_depth=0)
    params = init_params(jax.random.key(0), T.model_def(cfg))
    s = E.ServeSession(params, cfg, num_slots=2, max_seq=32,
                       prefill_chunk=8)
    for r in (Request(rid=0, prompt_len=12, max_new_tokens=3),
              Request(rid=1, prompt_len=6, max_new_tokens=2)):
        s.submit(r)
    s.step_round()                    # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            s.step_round()
    spans = _host_spans(str(tmp_path))
    rounds = [sp for sp in spans if sp[2] == C.ROUND_SPAN]
    assert [r[3]["step_num"] for r in rounds] == [1, 2, 3, 4]
    order = {n: i for i, n in enumerate(C.ROUND_STAGE_SPANS)}
    saw = set()
    for r0, r1, _, _ in rounds:
        kids = [sp for sp in spans
                if sp[2] != C.ROUND_SPAN and r0 <= sp[0] and sp[1] <= r1]
        names = [k[2] for k in kids]
        assert names == sorted(names, key=order.__getitem__)
        assert len(set(names)) == len(names)
        assert {"ess.admit", "ess.finish"} <= set(names)
        saw |= set(names)
        for k in kids:
            if k[2] == "ess.prefill":
                assert k[3]["rid"] in (0, 1)
    assert saw == set(C.ROUND_STAGE_SPANS)
