"""Serving launcher: the public `EssEngine` front-end over the compiled
continuous-batching ESS serve loop.

Laptop-scale demo of the full pipeline — chunked decode-interleaved
prefill, MTP speculative rounds, TBO, paged host tier — driven through
``EssEngine.generate`` (``--eager`` switches the underlying StepPrograms
to the op-by-op debugging path; the streams are identical, the rounds/s
are not).  Per-request knobs ride on ``SamplingParams``
(``--temperature/--top-k/--top-p``, ``--stop-token`` for early exit);
``metrics()`` reports the TokenEvent-derived latency percentiles.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v32-exp-ess-smoke \
      --requests 4 --prompt-len 48 --new-tokens 16 --mtp-depth 2 --tbo
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.api import EssEngine, SamplingParams


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v32-exp-ess-smoke")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--mtp-depth", type=int, default=0)
    ap.add_argument("--tbo", action="store_true")
    ap.add_argument("--eager", action="store_true",
                    help="op-by-op debugging path (compiled=False)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--stop-token", type=int, default=None,
                    help="terminate a stream early at this token id")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    assert cfg.ess.enabled, "serve.py demonstrates the ESS path"
    if args.mtp_depth > cfg.mtp_depth:
        cfg = dataclasses.replace(cfg, mtp_depth=args.mtp_depth)
    params = init_params(jax.random.key(args.seed), T.model_def(cfg))

    engine = EssEngine(
        params, cfg, num_slots=args.slots, max_seq=args.max_seq,
        prefill_chunk=args.prefill_chunk, mtp_depth=args.mtp_depth,
        tbo=args.tbo, compiled=not args.eager)
    sp = SamplingParams(
        max_tokens=args.new_tokens, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p,
        stop_token_ids=() if args.stop_token is None
        else (args.stop_token,))

    t0 = time.time()
    outs = engine.generate([args.prompt_len] * args.requests, sp,
                           max_rounds=4 * (args.new_tokens
                                           + args.prompt_len))
    dt = time.time() - t0
    report = engine.session.report
    m = engine.metrics()
    mode = "eager" if args.eager else "compiled"
    served = sum(o.finish_reason in ("length", "stop") for o in outs)
    print(f"[{mode}] {served}/{len(outs)} requests in "
          f"{report.rounds} decode rounds ({report.spec_rounds} "
          f"speculative), {dt:.2f}s wall")
    print(f"  {report.tokens_per_s:.1f} accepted-tok/s, "
          f"{report.rounds_per_s:.1f} rounds/s, "
          f"accept rate {report.accept_rate:.2f}; "
          f"prefill {report.prefill_tokens} toks in "
          f"{report.prefill_chunks} chunks")
    def fmt(v, spec):
        # a percentile is None when no event backs it (e.g. no
        # inter-token gaps at --new-tokens 1)
        return "n/a" if v is None else format(v, spec)
    print(f"  ttft p50/p95 {fmt(m['ttft_p50_s'], '.3f')}/"
          f"{fmt(m['ttft_p95_s'], '.3f')}s, "
          f"inter-token p50/p95 {fmt(m['itl_p50_s'], '.4f')}/"
          f"{fmt(m['itl_p95_s'], '.4f')}s")
    for o in outs:
        print(f"  rid{o.rid}: {o.n_generated} tokens "
              f"({o.finish_reason})  {o.tokens[:8]}"
              f"{'...' if o.n_generated > 8 else ''}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
