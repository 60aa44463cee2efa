"""esslint layer 2 — lower every StepProgram and audit the serve
contracts (:mod:`repro.analysis.contracts`).

Seven audits, each a thin driver over a pure checker (the checkers take
plain data so tests can exercise failure paths without lowering):

* **ESS101 donation** — every round program donates the EngineState
  (argnum 1); lowering must alias *all* of its leaves into outputs
  (``tf.aliasing_output`` in the StableHLO) and emit no "donated
  buffers ... not usable" warning.  A missed alias doubles peak cache
  memory silently.
* **ESS102 one-fetch** — driving a real session over a mixed workload,
  every serve round performs at most :data:`FETCH_BUDGET_PER_ROUND`
  ``jax.device_get`` calls and the total equals ``report.rounds``.
* **ESS103 retrace** — tracing a mixed workload (admissions,
  preemption, ragged chunks, MTP on/off) twice yields exactly one trace
  per ``(round kind, shape bucket)``; a second trace is a silent
  recompile in production.
* **ESS104 dtype drift** — each program's output EngineState leaf
  dtypes equal its input leaf dtypes, and no ``convert_element_type``
  widens a cache-tier-sized bf16 tensor to f32.
* **ESS105 no-blocking-stage** — with the async-offload pipeline on
  (``prefetch > 0``), a backward slice of each decode/spec jaxpr must
  show (a) the staged slab a round *consumes* feeding its tokens
  output, and (b) the slab *refill* gather needed only for the
  ``staged_rows`` output — a refill gather on the token path means the
  round blocks on a transfer it should have overlapped into the next
  round.
* **ESS106 tier dequant** — with a quantized host tier
  (``ess.host_cache_dtype != "bf16"``), no program widens a
  cache-tier-sized int8/fp8 tensor to bf16/f16/f32: dequantization
  happens strictly after the gather, at miss/slab width.  A tier-sized
  convert means some path materialized the whole decompressed tier —
  the exact blowup the compressed representation exists to avoid.
* **ESS107 one-handoff** — driving a PD-disaggregated
  :class:`~repro.cluster.EssCluster` (1 prefill + 1 decode worker),
  every migration is exactly one host-side page-pack
  (:data:`PACK_BUDGET_PER_MIGRATION` fetches at the allowlisted pack
  site), prefill rounds fetch only to pack, install performs zero
  fetches, and decode rounds stay within the ESS102 one-fetch budget —
  a smuggled second ``device_get`` anywhere in a worker round is
  caught.

Abstract lowering (ESS101/ESS104) uses ``ShapeDtypeStruct`` trees — no
parameter memory is allocated.  The workload audits (ESS102/ESS103)
initialize the smoke model.  Every audit draws a fresh ``max_seq`` from
a process-wide counter so the lru-cached ``get_programs`` and the
process-wide ``TRACE_COUNTS`` start cold for its shape family.
"""

from __future__ import annotations

import dataclasses
import itertools
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.analysis import contracts as C
from repro.analysis.findings import Finding

SMOKE_CONFIG = "deepseek-v32-exp-ess-smoke"

# each audit invocation claims a fresh shape family (max_seq) so
# lru-cached programs/trace counters never alias across audits or tests
_FRESH_SEQ = itertools.count(61)

_ALIAS_ATTR = "tf.aliasing_output"
_AUDIT_PATH = "<jaxpr>"


def _smoke_cfg(paged: bool = True, host_dtype: str = "bf16"):
    from repro.configs import get_config
    cfg = get_config(SMOKE_CONFIG)
    ess = dataclasses.replace(cfg.ess, max_miss_ratio=1.0,
                              host_cache_dtype=host_dtype,
                              **({} if paged else {"paged_host": False}))
    return dataclasses.replace(cfg, ess=ess, mtp_depth=2)


def _abstract_state(cfg, num_slots: int, max_seq: int,
                    prefetch: int = 0):
    from repro.cache import latent_cache as LC
    from repro.serving import state as ES

    paged = LC.uses_paged_host(cfg)
    num_pages = num_slots * LC.num_blocks(cfg, max_seq) if paged else None

    def build():
        caches = LC.init_ess_caches(cfg, num_slots, max_seq,
                                    cfg.param_dtype, num_pages=num_pages,
                                    map_slots=not paged)
        return ES.init_engine_state(cfg, caches, num_slots,
                                    prefetch_rows=prefetch)

    return jax.eval_shape(build)


def _abstract_params(cfg):
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    return abstract_params(T.model_def(cfg))


@dataclasses.dataclass
class AuditTarget:
    kind: str                   # "decode" | "spec" | "prefill/C4last1" ...
    fn: Callable                # donated jitted round program
    args: tuple                 # abstract arguments (ShapeDtypeStructs)
    state: object               # abstract EngineState (args[1])


def build_targets(cfg=None, *, num_slots: int = 2,
                  max_seq: Optional[int] = None, mtp_depth: int = 2,
                  prefill_chunk: int = 8,
                  prefetch: int = 0) -> list[AuditTarget]:
    """Every round-program variant of one shape family, with abstract
    arguments ready for ``.lower()`` / ``jax.eval_shape``.
    ``prefetch > 0`` builds the pipelined variants (staging slab in
    state, prefetch-aware step programs)."""
    from repro.serving import step as SP
    cfg = cfg if cfg is not None else _smoke_cfg()
    max_seq = max_seq if max_seq is not None else next(_FRESH_SEQ)
    params = _abstract_params(cfg)
    state = _abstract_state(cfg, num_slots, max_seq, prefetch)
    programs = SP.get_programs(cfg, num_slots, max_seq, False, False,
                               mtp_depth, prefetch)
    i32 = lambda shape=(): jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    targets = [AuditTarget("decode", programs.decode(True),
                           (params, state), state)]
    if mtp_depth > 0:
        targets.append(AuditTarget("spec", programs.spec(True),
                                   (params, state), state))
    chunk = 1
    chunks = []
    while chunk < prefill_chunk:
        chunks.append(chunk)
        chunk <<= 1
    chunks.append(prefill_chunk)
    for c in chunks:
        for last in (False, True):
            targets.append(AuditTarget(
                f"prefill/C{c}last{int(last)}",
                programs.prefill(c, last, True),
                (params, state, jax.ShapeDtypeStruct((1, c), jnp.int32),
                 i32(), i32()), state))
    return targets


# ---------------------------------------------------------------------------
# ESS101: donation
# ---------------------------------------------------------------------------

def check_donation(kind: str, n_aliased: int, n_state_leaves: int,
                   warning_msgs: list[str]) -> list[Finding]:
    """Pure checker: aliasing attr count vs donated leaf count + any
    donation warnings captured during lowering."""
    out = []
    bad = [m for m in warning_msgs if "donat" in m.lower()]
    if bad:
        out.append(Finding(
            rule="ESS101", path=_AUDIT_PATH, line=0, scope=kind,
            message=f"unusable donation while lowering {kind}: {bad[0]}"))
    if n_aliased < n_state_leaves:
        out.append(Finding(
            rule="ESS101", path=_AUDIT_PATH, line=0, scope=kind,
            message=f"{kind}: only {n_aliased}/{n_state_leaves} donated "
                    f"EngineState leaves aliased into outputs — the rest "
                    f"are silently copied (peak memory doubles)"))
    return out


def audit_donation(cfg=None, *, targets=None, **kw) -> list[Finding]:
    findings = []
    for t in (targets if targets is not None
              else build_targets(cfg, **kw)):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            text = t.fn.lower(*t.args).as_text()
        findings += check_donation(
            t.kind, text.count(_ALIAS_ATTR),
            len(jax.tree.leaves(t.state)),
            [str(x.message) for x in w])
    return findings


# ---------------------------------------------------------------------------
# ESS102: one fetch per round
# ---------------------------------------------------------------------------

def check_fetch_counts(per_round: list[int], rounds: int,
                       budget: int = C.FETCH_BUDGET_PER_ROUND
                       ) -> list[Finding]:
    """Pure checker over per-serve-round device_get counts."""
    out = []
    for i, n in enumerate(per_round):
        if n > budget:
            out.append(Finding(
                rule="ESS102", path=_AUDIT_PATH, line=0,
                scope=f"round[{i}]",
                message=f"{n} device->host fetches in one serve round "
                        f"(budget {budget})"))
    total = sum(per_round)
    if total != rounds:
        out.append(Finding(
            rule="ESS102", path=_AUDIT_PATH, line=0, scope="total",
            message=f"{total} fetches over {rounds} decode rounds — the "
                    f"packed RoundOut fetch must be the only transfer "
                    f"(expected exactly {rounds})"))
    return out


def _mixed_requests():
    from repro.serving.scheduler import Request
    return [Request(rid=0, prompt_len=11, max_new_tokens=5),
            Request(rid=1, prompt_len=8, max_new_tokens=4),
            Request(rid=2, prompt_len=9, max_new_tokens=3,
                    temperature=0.9, seed=5),
            Request(rid=3, prompt_len=10, max_new_tokens=4)]


def audit_fetch_counts(cfg=None, *, session_cls=None, mtp_depth: int = 0,
                       max_seq: Optional[int] = None,
                       overlap: bool = False) -> list[Finding]:
    """Drive a real mixed workload counting ``jax.device_get`` per serve
    round.  ``session_cls`` is injectable so tests can demonstrate the
    audit catching a session that sneaks extra fetches.  ``overlap=True``
    drives the pipelined session — async staging must ride the same
    single packed fetch, not add host syncs."""
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving import engine as E
    cfg = cfg if cfg is not None else _smoke_cfg()
    session_cls = session_cls or E.ServeSession
    max_seq = max_seq if max_seq is not None else next(_FRESH_SEQ)
    params = init_params(jax.random.key(0), T.model_def(cfg))
    session = session_cls(params, cfg, num_slots=2, max_seq=max_seq,
                          prefill_chunk=8, compiled=True,
                          mtp_depth=mtp_depth, overlap=overlap)
    for r in _mixed_requests():
        session.submit(r)
    counts = []
    real = jax.device_get
    calls = [0]

    def counting(*a, **k):
        calls[0] += 1
        return real(*a, **k)

    jax.device_get = counting
    try:
        guard = 100
        while (session.sched.running or session.sched.queue) and guard:
            before = calls[0]
            session.step_round()
            counts.append(calls[0] - before)
            guard -= 1
    finally:
        jax.device_get = real
    if not guard:
        return [Finding(rule="ESS102", path=_AUDIT_PATH, line=0,
                        scope="driver",
                        message="workload did not finish in 100 rounds")]
    return check_fetch_counts(counts, session.report.rounds)


# ---------------------------------------------------------------------------
# ESS103: retrace budget
# ---------------------------------------------------------------------------

def check_retrace(deltas: dict[str, int]) -> list[Finding]:
    """Pure checker over per-program trace-count deltas."""
    out = []
    if not deltas:
        return [Finding(rule="ESS103", path=_AUDIT_PATH, line=0,
                        scope="driver",
                        message="no programs traced — audit drove nothing")]
    for key, n in sorted(deltas.items()):
        if n != 1:
            out.append(Finding(
                rule="ESS103", path=_AUDIT_PATH, line=0, scope=key,
                message=f"traced {n}x (expected once): a retrace per "
                        f"round is a silent recompile in production"))
    kinds = {k.split("/")[0] for k in deltas}
    missing = set(C.ROUND_KINDS) - kinds
    if missing:
        out.append(Finding(
            rule="ESS103", path=_AUDIT_PATH, line=0, scope="coverage",
            message=f"round kinds never traced by the audit workload: "
                    f"{sorted(missing)}"))
    return out


def audit_retrace(cfg=None, *, max_seq: Optional[int] = None
                  ) -> list[Finding]:
    """Trace a mixed workload twice (admissions, a preemption, ragged
    final chunks, MTP off/on) in a fresh shape family; every program
    must trace exactly once."""
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving import engine as E
    from repro.serving import step as SP
    cfg = cfg if cfg is not None else _smoke_cfg()
    max_seq = max_seq if max_seq is not None else next(_FRESH_SEQ)
    params = init_params(jax.random.key(0), T.model_def(cfg))
    sig = f"s{max_seq}tbo"
    before = {k: v for k, v in SP.TRACE_COUNTS.items() if sig in k}

    def drive(mtp_depth):
        s = E.ServeSession(params, cfg, num_slots=2, max_seq=max_seq,
                           prefill_chunk=8, compiled=True,
                           mtp_depth=mtp_depth)
        for r in _mixed_requests():
            s.submit(dataclasses.replace(r))
        s.step_round(); s.step_round(); s.step_round()
        s.preempt(0)
        s.run(max_rounds=100)

    drive(0)
    drive(2)          # same shape family, spec program added
    drive(0)          # third session: pure program-cache hits
    deltas = {k: v - before.get(k, 0)
              for k, v in SP.TRACE_COUNTS.items()
              if sig in k and v != before.get(k, 0)}
    return check_retrace(deltas)


# ---------------------------------------------------------------------------
# ESS104: dtype drift
# ---------------------------------------------------------------------------

def check_state_dtypes(kind: str, in_dtypes: list, out_dtypes: list
                       ) -> list[Finding]:
    """Pure checker: per-leaf dtype round-trip through a program."""
    out = []
    if len(in_dtypes) != len(out_dtypes):
        return [Finding(
            rule="ESS104", path=_AUDIT_PATH, line=0, scope=kind,
            message=f"{kind}: state leaf count changed "
                    f"{len(in_dtypes)} -> {len(out_dtypes)}")]
    for i, (a, b) in enumerate(zip(in_dtypes, out_dtypes)):
        if a != b:
            out.append(Finding(
                rule="ESS104", path=_AUDIT_PATH, line=0, scope=kind,
                message=f"{kind}: state leaf[{i}] dtype drifts "
                        f"{a} -> {b} across the round"))
    return out


def _jaxpr_subfuns(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _iter_eqns(jaxpr):
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(_jaxpr_subfuns(eqn.params))


def find_big_upcasts(closed_jaxpr, threshold: int) -> list[tuple]:
    """(size, src_dtype, dst_dtype) for every convert_element_type that
    widens a bf16 tensor of >= ``threshold`` elements to f32."""
    hits = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        (src,), (dst,) = eqn.invars, eqn.outvars
        saval, daval = src.aval, dst.aval
        if (getattr(saval, "dtype", None) == jnp.bfloat16
                and daval.dtype == jnp.float32
                and saval.size >= threshold):
            hits.append((int(saval.size), str(saval.dtype),
                         str(daval.dtype)))
    return hits


def audit_dtypes(cfg=None, *, targets=None, **kw) -> list[Finding]:
    findings = []
    for t in (targets if targets is not None
              else build_targets(cfg, **kw)):
        in_leaves = jax.tree.leaves(t.state)
        out_shapes = jax.eval_shape(t.fn, *t.args)
        out_state = out_shapes[0]       # every round fn returns (state, ...)
        findings += check_state_dtypes(
            t.kind, [str(x.dtype) for x in in_leaves],
            [str(x.dtype) for x in jax.tree.leaves(out_state)])
        # cache-tier threshold: the largest cache-tier state leaf.  On a
        # raw tier that is the bf16 host latent; on a quantized tier the
        # payload is int8/fp8 but its *element count* still defines
        # "tier-sized" — otherwise the threshold collapses to chunk-scale
        # bf16 leaves and legitimate per-step f32 math trips the audit.
        bf16_sizes = [x.size for x in in_leaves
                      if x.dtype == jnp.bfloat16
                      or str(x.dtype) in C.ESS106_NARROW_DTYPES]
        if not bf16_sizes:
            continue
        threshold = max(bf16_sizes)
        jaxpr = jax.make_jaxpr(t.fn)(*t.args)
        for size, sd, dd in find_big_upcasts(jaxpr, threshold):
            findings.append(Finding(
                rule="ESS104", path=_AUDIT_PATH, line=0, scope=t.kind,
                message=f"{t.kind}: convert_element_type {sd}->{dd} on a "
                        f"cache-tier-sized tensor ({size} elements) — "
                        f"silent 2x memory/bandwidth"))
    return findings


# ---------------------------------------------------------------------------
# ESS106: quantized tier dequantizes at gather width only
# ---------------------------------------------------------------------------

def find_big_dequants(closed_jaxpr, threshold: int) -> list[tuple]:
    """(size, src_dtype, dst_dtype) for every convert_element_type that
    widens an int8/fp8 tensor of >= ``threshold`` elements to a float
    type (:data:`contracts.ESS106_WIDE_DTYPES`)."""
    hits = []
    for eqn in _iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        (src,), (dst,) = eqn.invars, eqn.outvars
        saval, daval = src.aval, dst.aval
        if (str(getattr(saval, "dtype", "")) in C.ESS106_NARROW_DTYPES
                and str(daval.dtype) in C.ESS106_WIDE_DTYPES
                and saval.size >= threshold):
            hits.append((int(saval.size), str(saval.dtype),
                         str(daval.dtype)))
    return hits


def check_tier_dequants(kind: str, hits: list[tuple],
                        threshold: int) -> list[Finding]:
    """Pure checker over one program's tier-sized dequant hits."""
    return [Finding(
        rule="ESS106", path=_AUDIT_PATH, line=0, scope=kind,
        message=f"{kind}: convert_element_type {sd}->{dd} on a "
                f"cache-tier-sized tensor ({size} >= {threshold} "
                f"elements) — the quantized tier must dequantize at "
                f"gather width, never materialize decompressed")
        for size, sd, dd in hits]


def audit_tier_dequant(cfg=None, *, targets=None, **kw) -> list[Finding]:
    """ESS106: with a quantized host tier, no StepProgram materializes a
    tier-sized bf16/f32 tensor from the int8/fp8 payload — dequant stays
    at miss/slab width inside the gather path."""
    findings = []
    for t in (targets if targets is not None
              else build_targets(cfg, **kw)):
        q_sizes = [x.size for x in jax.tree.leaves(t.state)
                   if str(x.dtype) in C.ESS106_NARROW_DTYPES]
        if not q_sizes:
            findings.append(Finding(
                rule="ESS106", path=_AUDIT_PATH, line=0, scope=t.kind,
                message=f"{t.kind}: no quantized state leaf — audit the "
                        f"quantized tier config (host_cache_dtype)"))
            continue
        threshold = max(q_sizes)
        jaxpr = jax.make_jaxpr(t.fn)(*t.args)
        findings += check_tier_dequants(
            t.kind, find_big_dequants(jaxpr, threshold), threshold)
    return findings


# ---------------------------------------------------------------------------
# ESS105: no blocking stage (pipeline overlap)
# ---------------------------------------------------------------------------

def _slice_jaxpr(jaxpr, out_positions: set) -> tuple[set, set]:
    """Backward slice: which invar positions and which gather equations
    (by ``id``) are needed to compute ``jaxpr.outvars[i]`` for the given
    positions.

    Descends *precisely* into arity-matched ``jit`` calls (only the
    needed inner outputs propagate demand to the outer inputs) and
    *conservatively* into every other call-like primitive — cond / scan /
    while mark all their invars needed and count every gather in every
    branch.  Conservatism only widens the needed sets, so a clean
    verdict ("this gather is exclusive to the slab output") is sound.
    """
    needed = set()
    for i in out_positions:
        v = jaxpr.outvars[i]
        if not isinstance(v, Literal):
            needed.add(v)
    gathers: set = set()
    for eqn in reversed(jaxpr.eqns):
        if not any(v in needed for v in eqn.outvars):
            continue
        sub = eqn.params.get("jaxpr") \
            if eqn.primitive.name == "jit" else None
        if (sub is not None
                and len(sub.jaxpr.invars) == len(eqn.invars)
                and len(sub.jaxpr.outvars) == len(eqn.outvars)):
            sub_out = {i for i, v in enumerate(eqn.outvars) if v in needed}
            sub_in, sub_g = _slice_jaxpr(sub.jaxpr, sub_out)
            gathers |= sub_g
            for i in sub_in:
                v = eqn.invars[i]
                if not isinstance(v, Literal):
                    needed.add(v)
        else:
            if eqn.primitive.name == "gather":
                gathers.add(id(eqn))
            for j in _jaxpr_subfuns(eqn.params):
                for se in _iter_eqns(j):
                    if se.primitive.name == "gather":
                        gathers.add(id(se))
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    needed.add(v)
    invar_positions = {i for i, v in enumerate(jaxpr.invars) if v in needed}
    return invar_positions, gathers


def check_pipeline_overlap(kind: str, *, consumes_staged: bool,
                           n_exclusive_gathers: int) -> list[Finding]:
    """Pure checker over the two sliced facts of one round program."""
    out = []
    if not consumes_staged:
        out.append(Finding(
            rule="ESS105", path=_AUDIT_PATH, line=0, scope=kind,
            message=f"{kind}: the staged_rows input never reaches the "
                    f"tokens output — the pipeline stages rows the round "
                    f"does not consume (dead prefetch)"))
    if n_exclusive_gathers < 1:
        out.append(Finding(
            rule="ESS105", path=_AUDIT_PATH, line=0, scope=kind,
            message=f"{kind}: no gather is exclusive to the staged_rows "
                    f"output — the slab refill sits on the token critical "
                    f"path, so the round blocks on its own prefetch "
                    f"instead of overlapping it into the next round"))
    return out


def audit_pipeline_overlap(cfg=None, *, targets=None, **kw
                           ) -> list[Finding]:
    """Slice each pipelined decode/spec program and verify the staging
    contract (:data:`contracts.ESS105_STAGED_ROWS_LEAF`): consumed slab
    on the token path, refill gather off it."""
    if targets is None:
        kw.setdefault("prefetch", 4)
        targets = build_targets(cfg, **kw)
    findings = []
    for t in targets:
        if t.kind not in ("decode", "spec"):
            continue
        if getattr(t.state, "staged_rows", None) is None:
            findings.append(Finding(
                rule="ESS105", path=_AUDIT_PATH, line=0, scope=t.kind,
                message=f"{t.kind}: no staging slab in EngineState — "
                        f"build targets with prefetch > 0"))
            continue
        n_params = len(jax.tree.leaves(t.args[0]))
        n_state = len(jax.tree.leaves(t.state))
        jaxpr = jax.make_jaxpr(t.fn)(*t.args).jaxpr
        # flattened invars = params then state; outvars = state then
        # RoundOut (tokens first).  staged_rows is pinned to the state
        # tail (contracts.ESS105_STAGED_ROWS_LEAF).
        rows_in = n_params + n_state + C.ESS105_STAGED_ROWS_LEAF
        tok_in, tok_g = _slice_jaxpr(jaxpr, {n_state})
        _, slab_g = _slice_jaxpr(
            jaxpr, {n_state + C.ESS105_STAGED_ROWS_LEAF})
        findings += check_pipeline_overlap(
            t.kind, consumes_staged=rows_in in tok_in,
            n_exclusive_gathers=len(slab_g - tok_g))
    return findings


# ---------------------------------------------------------------------------
# ESS107: one-handoff migration pack (PD cluster)
# ---------------------------------------------------------------------------

def check_migration_packs(pack_fetches: list[int],
                          packs_per_rid: dict[int, int],
                          prefill_extra: list[int],
                          decode_counts: list[int], decode_rounds: int,
                          stray: int = 0,
                          budget: int = C.PACK_BUDGET_PER_MIGRATION
                          ) -> list[Finding]:
    """Pure checker over the fetch accounting of one PD cluster run:
    every migration pack is exactly ``budget`` fetches, every migrated
    rid packs once, prefill rounds fetch only to pack, decode rounds
    stay within the one-fetch round budget, and nothing fetches outside
    a worker round (install is zero-fetch)."""
    out = []
    for i, n in enumerate(pack_fetches):
        if n != budget:
            out.append(Finding(
                rule="ESS107", path=_AUDIT_PATH, line=0,
                scope=f"pack[{i}]",
                message=f"{n} device->host fetches in one migration pack "
                        f"(budget {budget}: pages + scales + ikeys + "
                        f"hidden + t0 ride ONE packed fetch)"))
    for rid, n in sorted(packs_per_rid.items()):
        if n != 1:
            out.append(Finding(
                rule="ESS107", path=_AUDIT_PATH, line=0,
                scope=f"rid[{rid}]",
                message=f"rid={rid} packed {n} times — one handoff per "
                        f"migration"))
    for i, n in enumerate(prefill_extra):
        if n > 0:
            out.append(Finding(
                rule="ESS107", path=_AUDIT_PATH, line=0,
                scope=f"prefill_round[{i}]",
                message=f"{n} device->host fetches outside the pack site "
                        f"in a prefill worker round — prefill fetches "
                        f"only to pack"))
    for f in check_fetch_counts(decode_counts, decode_rounds):
        out.append(dataclasses.replace(
            f, rule="ESS107", scope=f"decode_{f.scope}"))
    if stray:
        out.append(Finding(
            rule="ESS107", path=_AUDIT_PATH, line=0, scope="cluster",
            message=f"{stray} device->host fetches outside any worker "
                    f"round (placement/install must perform zero "
                    f"fetches — the first token rides the packet)"))
    return out


def audit_migration_packs(cfg=None, *, decode_session_cls=None,
                          max_seq: Optional[int] = None) -> list[Finding]:
    """Drive a 1-prefill + 1-decode :class:`EssCluster` over the mixed
    workload, counting ``jax.device_get`` and bracketing every
    ``pack_migration`` call (the allowlisted ESS107 pack site,
    :data:`contracts.PACK_SITE`) and every worker round.
    ``decode_session_cls`` is injectable so tests can demonstrate the
    audit catching a decode round that smuggles a second fetch."""
    from repro.cluster import EssCluster
    from repro.cluster import kv_transfer as KT
    from repro.models import transformer as T
    from repro.models.params import init_params
    from repro.serving.api import SamplingParams
    cfg = cfg if cfg is not None else _smoke_cfg()
    max_seq = max_seq if max_seq is not None else next(_FRESH_SEQ)
    params = init_params(jax.random.key(0), T.model_def(cfg))
    cluster = EssCluster(params, cfg, num_prefill=1, num_decode=1,
                         num_slots=2, max_seq=max_seq, prefill_chunk=8,
                         compiled=True,
                         decode_session_cls=decode_session_cls)
    real = jax.device_get
    calls = [0]

    def counting(*a, **k):
        calls[0] += 1
        return real(*a, **k)

    pack_fetches: list[int] = []
    packs_per_rid: dict[int, int] = {}
    real_pack = KT.pack_migration

    def counting_pack(session, slot, req, t0, **kw):
        before = calls[0]
        pkt = real_pack(session, slot, req, t0, **kw)
        pack_fetches.append(calls[0] - before)
        packs_per_rid[req.rid] = packs_per_rid.get(req.rid, 0) + 1
        return pkt

    prefill_extra: list[int] = []
    decode_counts: list[int] = []

    def wrap_prefill(w):
        orig = w.step

        def step():
            before, npk = calls[0], len(pack_fetches)
            out = orig()
            prefill_extra.append(calls[0] - before
                                 - sum(pack_fetches[npk:]))
            return out

        w.step = step

    def wrap_decode(w):
        orig = w.step

        def step():
            before = calls[0]
            out = orig()
            decode_counts.append(calls[0] - before)
            return out

        w.step = step

    for w in cluster.prefill:
        wrap_prefill(w)
    for w in cluster.decode:
        wrap_decode(w)

    jax.device_get = counting
    KT.pack_migration = counting_pack
    try:
        for r in _mixed_requests():
            cluster.submit(r.prompt_len, SamplingParams(
                max_tokens=r.max_new_tokens, temperature=r.temperature,
                seed=r.seed))
        guard = 100
        while cluster.has_work() and guard:
            cluster.step()
            guard -= 1
        total_calls = calls[0]
    finally:
        jax.device_get = real
        KT.pack_migration = real_pack
    if not guard:
        return [Finding(rule="ESS107", path=_AUDIT_PATH, line=0,
                        scope="driver",
                        message="cluster workload did not finish in "
                                "100 steps")]
    stray = (total_calls - sum(pack_fetches) - sum(prefill_extra)
             - sum(decode_counts))
    return check_migration_packs(
        pack_fetches, packs_per_rid, prefill_extra, decode_counts,
        sum(w.session.report.rounds for w in cluster.decode), stray)


# ---------------------------------------------------------------------------
# the full audit
# ---------------------------------------------------------------------------

def run_all(*, paged: bool = True, dense: bool = True,
            workload: bool = True) -> list[Finding]:
    """Lower + audit both host tiers; ``workload=False`` skips the
    session-driving audits (ESS102/ESS103) for a fast structural pass."""
    findings = []
    tiers = ([("paged", _smoke_cfg(paged=True))] if paged else []) + \
            ([("dense", _smoke_cfg(paged=False))] if dense else [])
    for name, cfg in tiers:
        targets = build_targets(cfg)
        for f in (audit_donation(targets=targets)
                  + audit_dtypes(targets=targets)):
            findings.append(dataclasses.replace(
                f, scope=f"{name}/{f.scope}"))
    if paged:
        # pipelined (async-offload) variant of the paged tier: the
        # staging slab joins the donated state, so ESS101/ESS104 must
        # hold over the extra leaves, and ESS105 checks the refill
        # gather stays off the token critical path.
        cfg = _smoke_cfg(paged=True)
        targets = build_targets(cfg, prefetch=4)
        for f in (audit_donation(targets=targets)
                  + audit_dtypes(targets=targets)
                  + audit_pipeline_overlap(targets=targets)):
            findings.append(dataclasses.replace(
                f, scope=f"paged+pf/{f.scope}"))
        # quantized host tier (int8 payload + f16 scale plane): the
        # scale leaves join the donated state (ESS101/ESS104 over the
        # wider tree) and ESS106 proves dequant stays at gather width.
        # Audited plain and pipelined — the staging slab carries the
        # compressed representation, so the overlap contract (ESS105)
        # must hold with quantization on too.
        qcfg = _smoke_cfg(paged=True, host_dtype="int8")
        targets = build_targets(qcfg)
        for f in (audit_donation(targets=targets)
                  + audit_dtypes(targets=targets)
                  + audit_tier_dequant(targets=targets)):
            findings.append(dataclasses.replace(
                f, scope=f"paged+q8/{f.scope}"))
        targets = build_targets(qcfg, prefetch=4)
        for f in (audit_donation(targets=targets)
                  + audit_tier_dequant(targets=targets)
                  + audit_pipeline_overlap(targets=targets)):
            findings.append(dataclasses.replace(
                f, scope=f"paged+q8+pf/{f.scope}"))
    if workload:
        cfg = _smoke_cfg()
        for f in (audit_fetch_counts(cfg)
                  + audit_fetch_counts(cfg, mtp_depth=2)
                  + audit_retrace(cfg)):
            findings.append(dataclasses.replace(
                f, scope=f"paged/{f.scope}"))
        for f in audit_fetch_counts(cfg, overlap=True):
            findings.append(dataclasses.replace(
                f, scope=f"paged+pf/{f.scope}"))
        # PD disaggregation: the migration pack joins the fetch
        # discipline — one packed fetch per handoff, zero on install.
        for f in audit_migration_packs(cfg):
            findings.append(dataclasses.replace(
                f, scope=f"cluster/{f.scope}"))
    return findings
