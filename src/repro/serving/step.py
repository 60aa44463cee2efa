"""StepProgram builder: the serve round as donated, jitted XLA programs.

One serve round used to be traced op-by-op from Python — the 61-layer
unrolled decode re-dispatched every op every round, ``tok``/``hidden``
were rebuilt with per-slot ``.at[i].set`` loops, and every active slot
forced a host sync (``int(t)``, per-slot host-side sampling).  This
module compiles each round *kind* once per shape bucket:

* **decode**  — one Q=1 ESS step + in-device greedy/sampled token
  selection over the whole slot batch;
* **spec**    — the fused MTP round: ``mtp_draft`` + the Q=depth+1
  verify ``ess_decode`` + accept/rollback + token selection, all under
  one jit (TBO halves traced into the same program when enabled);
* **prefill** — one ``prefill_chunk`` step, shape-bucketed (ragged final
  chunks are zero-padded to the bucket and masked via ``n_valid``, so
  they never retrace), with the first-token draw in-device on the last
  chunk.

Each program takes ``(params, EngineState, ...)`` and **donates the
state** (``donate_argnums``): caches, token/hidden carries and sampling
knobs live on device round over round, and XLA aliases the big host
tier in place instead of keeping two copies.  The host's per-round
traffic collapses to one ``jax.device_get`` of the packed
:class:`~repro.serving.state.RoundOut`.

**Mode parity by construction.**  Every round function is glue around a
small set of *jitted units* — the raw model step, the speculative core
(draft + verify + rollback), the prefill-chunk core, and the samplers.
``compiled=True`` jits the whole round function (the units inline into
one donated program); ``compiled=False`` executes the glue op-by-op but
still calls the *same jitted units*.  The glue is exclusively
bit-exact arithmetic (argmax, sort-free selects, integer updates,
scatter/gather), so the two modes emit bit-identical token streams:
all floating-point math runs under XLA compilation in both, with
identical subgraphs.  (Running the units op-by-op instead would NOT be
bit-stable — XLA's fusion contracts multiply-adds, so fused and
unfused executions of the same einsum chain differ in the last ulp and
long decodes eventually flip an argmax.)  Eager mode remains the
debugging path: per-round logits, caches and emission packing are all
visible at unit boundaries.

Programs are cached process-wide (``get_programs``) so every session
with the same ``(cfg, shape family)`` reuses the same executables.  Each
round function is named for its kind (``decode_round``, ``spec_round``,
``prefill_chunk``), which makes its XLA module name in a profiler trace
(``jit_decode_round``, ...); its stages run under ``ess.*`` named scopes
(README "Tracing").

``TRACE_COUNTS[key]`` increments inside each round-function body, i.e.
at *trace* time under jit — the recompile-count guard test asserts every
program traces exactly once per shape bucket.  (In eager mode the body
runs every round, so the counters are only meaningful for compiled
sessions.)
"""

from __future__ import annotations

import functools
from collections import Counter
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.serving import mtp as MTP
from repro.serving import tbo as TBO
from repro.serving.sampling import greedy, sample_batch, sample_one
from repro.serving.state import EngineState, RoundOut, promote_slot

# program key -> times the round function body was traced (jit) or run
# (eager).  Keys: f"{kind}/{sig}" — see StepPrograms._sig.
TRACE_COUNTS: Counter = Counter()


def chunk_bucket(ck: int, prefill_chunk: int) -> int:
    """Shape bucket for a (possibly ragged) prefill chunk: the smallest
    power of two >= ``ck``, capped at ``prefill_chunk``.  Bounds the
    number of prefill programs at O(log prefill_chunk) while keeping
    short prompts cheap (an 8-token prompt buckets to 8, not to a
    4096-wide padded chunk)."""
    b = 1
    while b < ck:
        b <<= 1
    return min(b, prefill_chunk)


def _make_raw_step(cfg: ArchConfig, use_kernel: bool, tbo: bool) -> Callable:
    """(params, tokens [B,Q], positions [B,Q], caches, slot_mask, staged)
    -> DecodeOut — the TBO-composed model step both round kinds share.
    ``staged`` (default None = synchronous) is the async-offload staging
    slab pair threaded down from the EngineState leaves."""
    from repro.serving import engine as E      # engine imports this module

    def one(p_, c_, t_, po_, ca_, slot_mask=None, staged=None):
        return E.ess_decode(p_, c_, t_, po_, ca_, use_kernel=use_kernel,
                            slot_mask=slot_mask, staged=staged)

    def raw(params, tokens, positions, caches, slot_mask, staged=None):
        if tbo and tokens.shape[0] >= 2:
            logits, merged, stats = TBO.tbo_step(
                one, params, cfg, tokens, positions, caches,
                slot_mask=slot_mask, staged=staged)
            return E.DecodeOut(logits, merged, stats)
        return one(params, cfg, tokens, positions, caches,
                   slot_mask=slot_mask, staged=staged)

    return raw


class _Units(NamedTuple):
    """The jitted floating-point cores both execution modes share."""
    step: Callable          # raw Q=1 model step
    spec: Callable | None   # draft + Q=depth+1 verify + rollback
    maybe_sample: Callable  # cond-gated per-slot draws (see below)
    sample_one: Callable


def _maybe_sample_fn(seed, emit_index, logits, temperature, top_k, top_p,
                     sample_mask, fallback):
    """Per-slot draws, skipped when every slot is greedy: the sampler
    costs two full-vocab sorts + softmax/cumsum per slot per round, and
    ``sample_mask`` is a runtime array XLA cannot DCE through — the cond
    keeps the all-greedy hot path (the default workload) free of it.
    Jitted as a unit (an *eager* ``lax.cond`` would retrace both
    branches every round); both modes share it, so streams stay
    bit-identical."""
    return jax.lax.cond(
        jnp.any(sample_mask),
        lambda: sample_batch(seed, emit_index, logits, temperature,
                             top_k, top_p),
        lambda: fallback)


def _maybe_sample(units: _Units, state: EngineState, logits, fallback):
    # gate on *live* sampling slots: a sampling request still streaming
    # its prefill (admitted, frozen) must not drag the all-greedy fast
    # path into full-vocab sampling for rounds whose draw it discards
    return units.maybe_sample(state.seed, state.emit_index, logits,
                              state.temperature, state.top_k, state.top_p,
                              state.sample_mask & state.slot_mask, fallback)


def _decode_round_fn(units: _Units, key: str) -> Callable:
    """Plain Q=1 round: step the live batch, select each slot's next
    token (greedy or sampled from the per-slot knob arrays)."""

    def decode_round(params, state: EngineState):
        TRACE_COUNTS[key] += 1
        caches = state.caches
        staged = None if state.staged_ids is None else \
            (state.staged_ids, state.staged_rows, state.staged_scales)
        with jax.named_scope("ess.embed"):
            tokens, positions = state.tok[:, None], caches.lens[:, None]
        out = units.step(params, tokens, positions, caches, state.slot_mask,
                         staged)
        # token selection and emission packing
        with jax.named_scope("ess.head"):
            logits = out.logits[:, -1]                         # [B,V]
            g = greedy(logits)
            smp = _maybe_sample(units, state, logits, g)
            t = jnp.where(state.sample_mask, smp, g)
            live = state.slot_mask
            upd = {} if staged is None else dict(
                staged_ids=out.stats["staged_ids"],
                staged_rows=out.stats["staged_rows"],
                staged_scales=out.stats.get("staged_scales"))
            new_state = state._replace(
                caches=out.caches,
                tok=jnp.where(live, t, state.tok),
                hidden=jnp.where(live[:, None], out.stats["hidden"][:, -1],
                                 state.hidden),
                emit_index=state.emit_index + live.astype(jnp.int32),
                **upd)
            ro = RoundOut(jnp.where(live, t, 0)[:, None],
                          live.astype(jnp.int32),
                          h2d_rows=out.stats["misses"].sum())
        if staged is not None:
            ro = ro._replace(pf_hits=out.stats["pf_hits"],
                             pf_misses=out.stats["pf_misses"],
                             pf_wasted=out.stats["pf_wasted"])
        return new_state, ro

    return decode_round


def _spec_round_fn(units: _Units, key: str) -> Callable:
    """Fused MTP round: the speculative core (draft + Q=depth+1 verify +
    accept/rollback) plus emission packing.  Greedy slots emit the
    accepted prefix + bonus; sampling slots force-reject their drafts
    inside ``speculative_step`` and draw from the verify step's
    position-0 logits (the exact Q=1 distribution) with the same
    ``(seed, emit_index)`` key the Q=1 program would fold."""

    def spec_round(params, state: EngineState):
        TRACE_COUNTS[key] += 1
        live = state.slot_mask
        staged = None if state.staged_ids is None else \
            (state.staged_ids, state.staged_rows, state.staged_scales)
        spec = units.spec(params, state.caches, state.tok, state.hidden,
                          live, state.sample_mask, staged)
        with jax.named_scope("ess.head"):
            # false branch reuses the verify step's own position-0 argmax
            smp = _maybe_sample(units, state, spec.logits[:, 0],
                                spec.tokens[:, 0])
            tokens = spec.tokens.at[:, 0].set(
                jnp.where(state.sample_mask, smp, spec.tokens[:, 0]))
            n_emit = jnp.where(
                live, jnp.where(state.sample_mask, 1, spec.n_accepted), 0)
            last = jnp.take_along_axis(tokens,
                                       jnp.maximum(n_emit - 1, 0)[:, None],
                                       axis=1)[:, 0]
            upd = {} if staged is None else dict(
                staged_ids=spec.stats["staged_ids"],
                staged_rows=spec.stats["staged_rows"],
                staged_scales=spec.stats.get("staged_scales"))
            new_state = state._replace(
                caches=spec.caches,
                tok=jnp.where(live, last, state.tok),
                hidden=jnp.where(live[:, None], spec.hidden, state.hidden),
                emit_index=state.emit_index + live.astype(jnp.int32),
                **upd)
            ro = RoundOut(jnp.where(live[:, None], tokens, 0), n_emit,
                          h2d_rows=spec.stats["misses"].sum())
        if staged is not None:
            ro = ro._replace(pf_hits=spec.stats["pf_hits"],
                             pf_misses=spec.stats["pf_misses"],
                             pf_wasted=spec.stats["pf_wasted"])
        return new_state, ro

    return spec_round


def _prefill_round_fn(chunk_core: Callable, units: _Units, last: bool,
                      key: str) -> Callable:
    """One shape-bucketed prefill chunk for a dynamically-indexed slot.
    On the last chunk the first token is selected in-device (greedy or
    sampled at emission index 0) and the slot is promoted inside the
    round: ``tok``/``hidden``/``emit_index``/``slot_mask`` flip so the
    host only fetches the one first-token scalar."""

    def prefill_chunk(params, state: EngineState, tokens, slot, n_valid):
        TRACE_COUNTS[key] += 1
        if not last:
            caches = chunk_core(params, state.caches, tokens, slot, n_valid)
            return state._replace(caches=caches), jnp.zeros((), jnp.int32)
        lg, caches, hid_last = chunk_core(params, state.caches, tokens,
                                          slot, n_valid)
        state = state._replace(caches=caches)
        # first-token selection and the slot's promotion
        with jax.named_scope("ess.head"):
            lg_last = lg[0, jnp.maximum(n_valid - 1, 0)]             # [V]
            g = greedy(lg_last)
            smp = units.sample_one(state.seed[slot], state.emit_index[slot],
                                   lg_last, state.temperature[slot],
                                   state.top_k[slot], state.top_p[slot])
            t0 = jnp.where(state.sample_mask[slot], smp, g)
            state = promote_slot(state, slot, t0, hid_last[0])
        return state, t0

    return prefill_chunk


def _make_chunk_core(cfg: ArchConfig, use_kernel: bool,
                     last: bool) -> Callable:
    from repro.serving import engine as E

    def core(params, caches, tokens, slot, n_valid):
        C = tokens.shape[1]
        with jax.named_scope("ess.embed"):
            start = jax.lax.dynamic_slice_in_dim(caches.lens, slot, 1)  # [1]
            positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        lg, caches, _, hid_last = E.ess_prefill_chunk(
            params, cfg, tokens, positions, caches, slot=slot,
            want_logits=last, collect_tail=0, use_kernel=use_kernel,
            n_valid=n_valid)
        if not last:
            return caches
        return lg, caches, hid_last

    return core


class _Variants(NamedTuple):
    jitted: Callable
    eager: Callable


def _variants(fn: Callable, donate: tuple[int, ...]) -> _Variants:
    return _Variants(jax.jit(fn, donate_argnums=donate), fn)


class StepPrograms:
    """The round programs of one ``(cfg, shape family)`` — shared across
    every session with the same key, so executables compile once per
    process.  Each accessor takes ``compiled`` and returns either the
    donated jit program or the identical glue function calling the same
    jitted units (eager mode)."""

    def __init__(self, cfg: ArchConfig, num_slots: int, max_seq: int,
                 use_kernel: bool, tbo: bool, depth: int,
                 prefetch: int = 0):
        self._cfg = cfg
        self._use_kernel = use_kernel
        # the cfg hash disambiguates two configs sharing a shape family
        # (e.g. paged vs dense at the same slots/max_seq) so each
        # program's trace counter stays its own; ``prefetch`` keys the
        # pipelined (async-offload) programs apart from the synchronous
        # ones — the state's slab leaves change the traced structure
        self._sig = (f"B{num_slots}s{max_seq}tbo{int(tbo)}"
                     f"d{depth}k{int(use_kernel)}p{prefetch}"
                     f"c{abs(hash(cfg)) % 16 ** 4:04x}")
        raw = _make_raw_step(cfg, use_kernel, tbo)

        spec_core = None
        if depth > 0:
            def spec_core_fn(params, caches, tok, hidden, slot_mask,
                             sample_mask, staged=None):
                def dec_fn(p_, c_, t_, po_, ca_):
                    return raw(p_, t_, po_, ca_, slot_mask, staged)
                return MTP.speculative_step(
                    dec_fn, params, cfg, caches, tok, hidden,
                    slot_mask=slot_mask, sample_mask=sample_mask,
                    depth=depth)
            spec_core = jax.jit(spec_core_fn)

        self._units = _Units(step=jax.jit(raw), spec=spec_core,
                             maybe_sample=jax.jit(_maybe_sample_fn),
                             sample_one=jax.jit(sample_one))
        self._decode = _variants(
            _decode_round_fn(self._units, f"decode/{self._sig}"), (1,))
        self._spec = _variants(
            _spec_round_fn(self._units, f"spec/{self._sig}"),
            (1,)) if depth > 0 else None
        self._prefill: dict[tuple[int, bool], _Variants] = {}

    def decode(self, compiled: bool) -> Callable:
        return self._decode.jitted if compiled else self._decode.eager

    def spec(self, compiled: bool) -> Callable:
        assert self._spec is not None
        return self._spec.jitted if compiled else self._spec.eager

    def prefill(self, C: int, last: bool, compiled: bool) -> Callable:
        v = self._prefill.get((C, last))
        if v is None:
            core = jax.jit(_make_chunk_core(self._cfg, self._use_kernel,
                                            last))
            v = _variants(
                _prefill_round_fn(core, self._units, last,
                                  f"prefill/C{C}last{int(last)}/{self._sig}"),
                (1,))
            self._prefill[(C, last)] = v
        return v.jitted if compiled else v.eager


@functools.lru_cache(maxsize=64)
def get_programs(cfg: ArchConfig, num_slots: int, max_seq: int,
                 use_kernel: bool, tbo: bool, depth: int,
                 prefetch: int = 0) -> StepPrograms:
    return StepPrograms(cfg, num_slots, max_seq, use_kernel, tbo, depth,
                        prefetch)
