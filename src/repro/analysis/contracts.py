"""The serve loop's statically checked contracts — single source of truth.

Everything the two analysis layers enforce is *declared* here so the
checks, the docs (ANALYSIS.md) and the tests reference one table instead
of each hard-coding its own copy.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# One-fetch contract (jaxpr/runtime audit + ESS002)
# ---------------------------------------------------------------------------

# Maximum host fetches (jax.device_get) per serve round.  A round's only
# fetch is the commit stage's packed (tokens, n_emit[, t0..., pf...])
# struct; the plan and compute stages, prefill chunks, admissions and
# scheduler bookkeeping perform none.  Async staging traffic (the
# prefetch slab refill) is traced *inside* the round program — it is
# device/host DMA scheduled by XLA, never a blocking host fetch, so it
# does not count against this budget.
FETCH_BUDGET_PER_ROUND = 1

# The allowlisted fetch sites: "<module path>::<qualname>" of functions
# that may call jax.device_get (ESS002).  Everything else needs an inline
# `# esslint: disable=ESS002`.  The pipelined round puts the packed fetch
# in the commit stage; plan/compute must stay fetch-free.
FETCH_SITES = {
    "repro/serving/engine.py::ServeSession._commit_round",
    # the PD handoff's serialization point: one packed device_get per
    # migration (pages + scale plane + indexer keys + first token + MTP
    # hidden) — see PACK_BUDGET_PER_MIGRATION / ESS107 below
    "repro/cluster/kv_transfer.py::pack_migration",
}

# ---------------------------------------------------------------------------
# Retrace budget (jaxpr audit)
# ---------------------------------------------------------------------------

# Round kinds traced by the StepPrograms; every (kind, signature) pair
# must trace exactly once per process however the workload interleaves
# admissions, preemptions, ragged chunks and MTP on/off.
ROUND_KINDS = ("decode", "spec", "prefill")

# Prefill shape buckets are powers of two up to prefill_chunk: at most
# log2(chunk)+1 buckets, times two trace keys (mid/last variants).
def max_prefill_trace_keys(prefill_chunk: int) -> int:
    n = 1
    b = 1
    while b < prefill_chunk:
        b <<= 1
        n += 1
    return 2 * n


# ---------------------------------------------------------------------------
# Donation contract (jaxpr audit)
# ---------------------------------------------------------------------------

# Every round program donates the EngineState pytree (argnum 1); lowering
# must alias *all* of its leaves into outputs (tf.aliasing_output) and
# emit no "donated buffers were not usable" warning.
DONATED_ARGNUM = 1

# ---------------------------------------------------------------------------
# Dtype contract (jaxpr audit)
# ---------------------------------------------------------------------------

# Latent/indexer-key tensors stay bf16 (cfg.param_dtype) end to end: each
# program's output state leaf dtypes equal its input leaf dtypes, and no
# convert_element_type widens a cache-sized bf16 operand to f32.
CACHE_DTYPE_INVARIANT = "state-out leaf dtypes == state-in leaf dtypes"

# ---------------------------------------------------------------------------
# ESS001: cache-mutating helpers require an explicit gating argument
# ---------------------------------------------------------------------------

# qualified callee -> keyword that must be passed explicitly (None is an
# accepted *explicit* value — the rule bans relying on a default, not the
# ungated mode itself).
ESS001_TARGETS = {
    "repro.core.offload.host_scatter_rows": "slot_mask",
    "repro.core.offload.host_scatter_rows_stacked": "slot_mask",
    "repro.core.offload.scatter_tier_rows": "slot_mask",
    "repro.core.offload.scatter_tier_rows_stacked": "slot_mask",
    "repro.core.lru_pool.lookup": "slot_mask",
    "repro.core.lru_pool.admit": "slot_mask",
    "repro.core.warmup.lru_warmup": "slot_mask",
    "repro.serving.engine.ess_decode": "slot_mask",
    "repro.serving.engine.ess_prefill_chunk": "n_valid",
    "repro.core.offload.gather_into_slab": "slot_mask",
    "repro.core.offload.scatter_from_slab": "slot_mask",
}

# ---------------------------------------------------------------------------
# ESS002 scope: serving/core/cache modules (training checkpoints etc. sync
# legitimately and are out of scope)
# ---------------------------------------------------------------------------

ESS002_MODULE_PREFIXES = ("repro/serving/", "repro/core/", "repro/cache/",
                          "repro/cluster/")

# ---------------------------------------------------------------------------
# ESS003 scope: traced round bodies (modules fully traced into the
# StepPrograms, plus the two traced entry points in engine.py)
# ---------------------------------------------------------------------------

# module relpath -> None (whole module traced) | set of function names
ESS003_TRACED_SCOPES = {
    "repro/core/lru_pool.py": None,
    "repro/core/overlap.py": None,
    "repro/core/warmup.py": None,
    "repro/serving/mtp.py": None,
    "repro/serving/tbo.py": None,
    "repro/serving/sampling.py": None,
    "repro/serving/step.py": None,
    "repro/serving/engine.py": {"ess_decode", "ess_prefill_chunk"},
    # transfer.py's traced halves (slab init / prefetch planning / slab
    # matching); the TransferEngine methods themselves are host-side
    # plumbing around them.
    "repro/core/transfer.py": {"empty_slab", "plan_prefetch",
                               "match_staged"},
}

# ESS003's host-side escape hatch: check_consistent is explicitly a
# host/debug helper inside an otherwise fully traced module
ESS003_HOST_FUNCTIONS = {"check_consistent"}

# ---------------------------------------------------------------------------
# ESS105: no blocking stage (pipeline-overlap audit)
# ---------------------------------------------------------------------------

# With the async-offload pipeline on, every round program must keep the
# staging slab off the token critical path:
#
#  (a) the slab a round *consumes* is the one staged by the previous
#      round — the ``staged_rows`` input leaf must feed the tokens
#      output (otherwise the pipeline never uses its prefetches and the
#      slab is dead weight), and
#  (b) the slab *refill* gather issued this round must be needed only
#      for the ``staged_rows`` output leaf, never for tokens — a refill
#      gather on the token path means the round blocks on a transfer it
#      should have overlapped into the next round's compute.
#
# The slab leaves are pinned to the END of EngineState (state.py keeps
# ``staged_ids``/``staged_scales``/``staged_rows`` as its last fields,
# rows last in *every* configuration — ``staged_scales`` is an empty
# pytree on a raw bf16 tier, so the rows index holds either way) so the
# audit can find them positionally in the flattened jaxpr
# invars/outvars.
ESS105_STAGED_ROWS_LEAF = -1  # EngineState leaf index, from the end

# ---------------------------------------------------------------------------
# ESS106: quantized tier dequantizes at gather width only
# ---------------------------------------------------------------------------

# With a quantized host latent tier (ess.host_cache_dtype != "bf16"), no
# StepProgram may widen a cache-tier-sized int8/fp8 tensor to
# bf16/f16/f32: dequantization happens strictly *after* the gather, at
# miss/slab width.  A tier-sized convert_element_type means some path
# materialized the whole decompressed tier — the exact
# memory-and-bandwidth blowup the compressed representation exists to
# avoid.  The threshold is the largest quantized state leaf (the host
# tier itself).
ESS106_NARROW_DTYPES = ("int8", "float8_e4m3fn", "float8_e5m2")
ESS106_WIDE_DTYPES = ("bfloat16", "float16", "float32")

# ---------------------------------------------------------------------------
# ESS107: one host-side page-pack per PD migration
# ---------------------------------------------------------------------------

# A prefill→decode handoff serializes a finished prompt's state exactly
# once: :func:`repro.cluster.kv_transfer.pack_migration` reads the
# slot's host pages, scale plane, indexer keys, first token and MTP
# hidden in ONE packed ``jax.device_get`` (the allowlisted pack site in
# FETCH_SITES).  The page inventory itself comes from the host-side
# allocator (``HostPageAllocator.owned``), so packing never needs a
# second fetch to discover *what* to move; and a decode worker's serve
# rounds keep the ordinary FETCH_BUDGET_PER_ROUND — installing a
# migration adds zero fetches on the decode side (the first token rides
# the packet).
PACK_BUDGET_PER_MIGRATION = 1
PACK_SITE = "repro/cluster/kv_transfer.py::pack_migration"

# ---------------------------------------------------------------------------
# Stage names in the profiler trace
# ---------------------------------------------------------------------------

# Device scopes (``jax.named_scope``) of the decode and prefill-chunk
# programs; every op of a decode round lies under exactly one of them
# (repro.analysis.hlo_scopes maps an optimized HLO module onto them).
# The MTP draft and accept stages of the spec round are not named yet.
DEVICE_SCOPES = ("ess.embed", "ess.indexer", "ess.topk", "ess.pool",
                 "ess.miss_gather", "ess.attend", "ess.spill",
                 "ess.prefetch", "ess.ffn", "ess.head")

# XLA module name of each round kind (the jitted round function's name)
ROUND_MODULES = {"decode": "jit_decode_round", "spec": "jit_spec_round",
                 "prefill": "jit_prefill_chunk"}

# Host spans (jax.profiler annotations) of ServeSession.step_round: the
# round itself (a StepTraceAnnotation whose step_num is the round index)
# and its stages in the order they run.  ``ess.prefill`` appears only in
# rounds that run a prefill chunk, the decode stages only in rounds with
# an active slot.
ROUND_SPAN = "ess.round"
ROUND_STAGE_SPANS = ("ess.admit", "ess.prefill", "ess.plan", "ess.launch",
                     "ess.fetch", "ess.commit", "ess.finish")
