#!/usr/bin/env python3
"""Bring-up smoke run of the ESS serve path on one TPU chip.

Drives ``EssEngine.generate`` over the compiled, donated StepPrograms at
the published widths of ``deepseek-v32-exp-ess`` (depth and expert count
cut to fit one v5e chip; weights random from ``--seed``) and checks what
comes out:

1. serve  — 6 requests of 4096 prompt tokens through 4 slots (admission
   and slot recycling run; 4096 > top-K 2048, so DSA selection is really
   sparse), 32 greedy tokens each: every request must end ``length``
   with exactly 32 in-vocabulary tokens;
2. logits — the last-position logits of one 1024-token prompt through
   the ESS prefill against the dense reference forward
   (``transformer.forward(mode="train")``), at the published top-K
   (covers the whole prompt) and at a sparse top-K where the indexer
   decides what is attended;
3. kernels — every Pallas kernel compiled for the chip (never interpret
   mode) at real widths against its ``ref.py`` oracle.

Earlier lines report the device, the cuts, parameter and peak bytes, the
host tier's memory kind, and compile / wall seconds (set-up facts of this
smoke run, not performance metrics).  The last line is one JSON object,
printed only when every phase passed.  Without a TPU it exits 1 and
prints no result.  Everything runs in this one process.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Phase 2 tolerance on the relative L2 error of the last-position logits,
# ESS prefill vs the dense reference.  Both run bf16 weights and bf16
# activations through 4 layers; they differ in reduction order, in where
# bf16 roundings fall (the ESS decode-side attention rounds its softmax
# weights to bf16), and, at a sparse top-K, in the rare near-tie indexer
# score that lands on the other side of the top-K boundary.  At reduced
# widths on the CPU this left 1.0e-2..1.6e-2 (dense top-K) and
# 1.9e-2..2.8e-2 (top-K 256), while the two controls below moved the
# logits by 0.51..0.81 (sparse vs dense selection) and 1.37..1.44 (no
# MLA output).  The bound sits ~3x above the first and ~6x below the
# second; both controls are checked to exceed it, so it can see a lost
# indexer selection or a lost attention output.
LOGIT_RTOL = 8e-2
SPARSE_TOPK = 256          # phase-2 indexer check: top-K << 1024 prompt


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def smoke_config():
    """``deepseek-v32-exp-ess`` at its published widths, cut to one chip:
    the 3 leading dense layers + 1 MoE layer, 8 of the 256 experts."""
    from repro.configs import get_config
    base = get_config("deepseek-v32-exp-ess")
    cfg = dataclasses.replace(
        base, num_layers=4,
        moe=dataclasses.replace(base.moe, num_experts=8))
    reduced = [
        f"num_layers {base.num_layers} -> 4 "
        f"({base.moe.first_dense_layers} dense + 1 MoE)",
        f"num_experts {base.moe.num_experts} -> 8: the router narrows to "
        f"8 outputs and top-{base.moe.top_k} of 8 selects every expert",
    ]
    return cfg, reduced


def rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def phase_serve(params, cfg, *, seed: int, num_slots: int = 4,
                max_seq: int = 8192, prefill_chunk: int = 512,
                n_requests: int = 6, prompt_len: int = 4096,
                max_tokens: int = 32) -> dict:
    import jax
    from repro.core import offload
    from repro.serving.api import EssEngine, SamplingParams

    check(prompt_len % prefill_chunk == 0,
          "prompt length is a multiple of the chunk (one prefill bucket)")
    eng = EssEngine(params, cfg, num_slots=num_slots, max_seq=max_seq,
                    prefill_chunk=prefill_chunk, mtp_depth=1)
    prompts = jax.random.randint(jax.random.key(seed + 1),
                                 (n_requests, prompt_len), 0, cfg.vocab_size)
    outs = eng.generate([prompts[i] for i in range(n_requests)],
                        SamplingParams(max_tokens=max_tokens),
                        # every round runs a prefill chunk or advances
                        # each running slot, so this bound is never hit
                        max_rounds=n_requests * (prompt_len // prefill_chunk
                                                 + max_tokens))
    for o in outs:
        check(o.finish_reason == "length",
              f"rid {o.rid} finished {o.finish_reason!r}, not 'length'")
        check(len(o.tokens) == max_tokens,
              f"rid {o.rid}: {len(o.tokens)} tokens, want {max_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in o.tokens),
              f"rid {o.rid}: token outside the vocabulary")
    rep = eng.session.report
    return {
        "host_latent_memory_kind":
            eng.session.state.caches.host_latent.sharding.memory_kind,
        "pinned_host_available": offload.host_available(),
        "rounds": rep.rounds, "spec_rounds": rep.spec_rounds,
        "prefill_chunks": rep.prefill_chunks,
        "accept_rate": rep.accept_rate,
        "finished": len(outs),
    }


def phase_logits(params, cfg, *, seed: int, prompt_len: int = 1024,
                 sparse_topk: int = SPARSE_TOPK) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serving import engine as E

    toks = jax.random.randint(jax.random.key(seed + 2), (1, prompt_len), 0,
                              cfg.vocab_size)
    pos = jnp.arange(prompt_len, dtype=jnp.int32)[None]

    # a cache at least top-K long, so top-K is not clipped by it
    max_seq = max(prompt_len, cfg.dsa.index_topk)

    def last_logits(c):
        ess = jax.jit(lambda p, t, q: E.ess_prefill(
            p, c, t, q, max_seq)[0][:, -1])
        ref = jax.jit(lambda p, t, q: T.forward(
            p, c, t, q, mode="train").logits[:, -1])
        return (jax.device_get(ess(params, toks, pos)),
                jax.device_get(ref(params, toks, pos)), ref)

    dense = cfg
    sparse = dataclasses.replace(
        cfg, dsa=dataclasses.replace(cfg.dsa, index_topk=sparse_topk))
    check(prompt_len <= dense.dsa.index_topk,
          "published top-K covers the whole prompt")
    ess_d, ref_d, ref_fn = last_logits(dense)
    ess_s, ref_s, _ = last_logits(sparse)
    # control: the reference with every layer's attention output removed
    no_mla = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if any(getattr(k, "key", None) == "wo" for k in path) else x, params)
    ref_no_mla = jax.device_get(ref_fn(no_mla, toks, pos))
    out = {
        "dense_topk_ess_vs_ref": rel_err(ess_d, ref_d),
        "sparse_topk_ess_vs_ref": rel_err(ess_s, ref_s),
        "control_sparse_vs_dense_ref": rel_err(ref_s, ref_d),
        "control_no_mla_vs_ref": rel_err(ref_no_mla, ref_d),
        "tolerance": LOGIT_RTOL,
    }
    for k in ("dense_topk_ess_vs_ref", "sparse_topk_ess_vs_ref"):
        check(out[k] < LOGIT_RTOL, f"{k} = {out[k]:.3e} >= {LOGIT_RTOL}")
    for k in ("control_sparse_vs_dense_ref", "control_no_mla_vs_ref"):
        check(out[k] > LOGIT_RTOL,
              f"{k} = {out[k]:.3e} <= {LOGIT_RTOL}: the tolerance cannot "
              f"see this")
    return out


def phase_kernels(*, seed: int) -> dict:
    """Each Pallas kernel compiled for the chip at real widths vs its
    pure-jnp oracle.  Data movement must match exactly; the two MXU
    kernels within 1e-2 of the f32 oracle's magnitude (the chip may run
    an f32 matmul as one bf16 pass)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed import compression as cmp
    from repro.kernels.gather_cache import ops as gops, ref as gref
    from repro.kernels.indexer import ref as iref
    from repro.kernels.indexer.indexer import indexer_scores_kernel
    from repro.kernels.sparse_mla import ops as sops, ref as sref

    ks = jax.random.split(jax.random.key(seed + 3), 8)
    bf = jnp.bfloat16

    def scaled_err(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.abs(a - b).max() / np.abs(b).max())

    out = {}
    # lightning indexer: Hi=64, Di=128 over S=8192 keys
    Hi, Di, S = 64, 128, 8192
    q = jax.random.normal(ks[0], (Hi, Di), bf)
    w = jax.random.normal(ks[1], (Hi,), bf)
    keys = jax.random.normal(ks[2], (S, Di), bf)
    valid = jnp.arange(S) < S - 100
    got = jax.jit(indexer_scores_kernel)(q, w, keys, valid)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(iref.indexer_scores_ref)(q, w, keys, valid)
    got, want = np.asarray(got), np.asarray(want)
    check(bool(((got <= -1e37) == (want <= -1e37)).all()),
          "indexer: invalid keys masked")
    v = want > -1e37
    out["indexer_scaled_err"] = scaled_err(got[v], want[v])

    # sparse MLA partial: H=128, D=576, K=2048 rows, rank 512
    H, D, K, R = 128, 576, 2048, 512
    qc = jax.random.normal(ks[3], (1, 1, H, D), bf)
    rows = jax.random.normal(ks[4], (1, K, D), bf)
    rvalid = jax.random.bernoulli(ks[5], 0.9, (1, K)).at[:, 0].set(True)
    scale = D ** -0.5
    p = sops.partial_attend(qc, rows, rvalid, scale, R)
    with jax.default_matmul_precision("highest"):
        o, m, l = jax.jit(sref.sparse_mla_partial_ref, static_argnums=(3, 4))(
            qc[0, 0], rows[0], rvalid[0], scale, R)
    out["sparse_mla_scaled_err"] = scaled_err(
        sref.finalize_ref(p.o[0, 0], p.m[0, 0], p.l[0, 0]),
        sref.finalize_ref(o, m, l))
    out["sparse_mla_max_err"] = scaled_err(p.m[0, 0], m)
    for k in ("indexer_scaled_err", "sparse_mla_scaled_err",
              "sparse_mla_max_err"):
        check(out[k] < 1e-2, f"{k} = {out[k]:.3e}")

    # gathers: D=576 latent rows out of an 8192-row cache, M=256 ids
    cache = jax.random.normal(ks[6], (S, D), bf)
    ids = jax.random.randint(ks[7], (256,), -8, S)
    got = gops.gather_rows(cache, ids)
    want = jnp.where((ids >= 0)[:, None], gref.gather_rows_ref(cache, ids), 0)
    check(bool((np.asarray(got) == np.asarray(want)).all()),
          "gather_rows == ref")
    qp, sc = cmp.quantize_rows(cache, jnp.int8)
    got = gops.gather_rows_dequant(qp, sc, ids)
    want = jnp.where((ids >= 0)[:, None],
                     gref.gather_rows_dequant_ref(qp, sc, ids), 0)
    check(bool((np.asarray(got) == np.asarray(want)).all()),
          "gather_rows_dequant == ref")
    pages = jax.random.randint(ks[7], (16,), 0, S // 64)
    check(bool((np.asarray(gops.gather_pages(cache, pages, 64))
                == np.asarray(gref.gather_row_blocks_ref(cache, pages, 64))
                ).all()), "gather_pages == ref")
    check(bool((np.asarray(gops.gather_pages_dequant(qp, sc, pages, 64))
                == np.asarray(gref.gather_row_blocks_dequant_ref(
                    qp, sc, pages, 64))).all()),
          "gather_pages_dequant == ref")
    out["gathers_exact"] = True
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1

    from repro.kernels.common import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T
    from repro.models.params import init_params, param_bytes

    check(not default_interpret(), "Pallas kernels compile (no interpret)")
    log(f"cache dir: {enable_compile_cache()}")
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event.endswith("backend_compile_duration"):
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    log(f"device_kind: {dev.device_kind}  (platform {dev.platform}, "
        f"{len(jax.devices())} device(s))")
    cfg, reduced = smoke_config()
    log(f"config: {cfg.name} widths, reduced: {json.dumps(reduced)}")
    defs = T.model_def(cfg)
    t0 = time.perf_counter()
    params = init_params(jax.random.key(args.seed), defs)
    jax.block_until_ready(params)
    log(f"param bytes: {param_bytes(defs)}  "
        f"(init {time.perf_counter() - t0:.1f}s, smoke set-up time)")

    phases = [("serve", lambda: phase_serve(params, cfg, seed=args.seed)),
              ("logits", lambda: phase_logits(params, cfg, seed=args.seed)),
              ("kernels", lambda: phase_kernels(seed=args.seed))]
    for i, (name, run) in enumerate(phases, 1):
        c0, t0 = compile_s[0], time.perf_counter()
        info = run()
        log(f"phase {i} {name}: pass  {json.dumps(info)}")
        log(f"phase {i} {name}: smoke times (not metrics): compile "
            f"{compile_s[0] - c0:.1f}s, wall {time.perf_counter() - t0:.1f}s;"
            f" peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
