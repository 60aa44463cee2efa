#!/usr/bin/env python3
"""Where the time of a decode round goes, stage by stage, in one traced
run of a benchmark cell.

    python bench/stage_trace.py --workload <cell> --seed <n> [--out DIR]

The run is ``bench/run.py``'s own traced run (``--trace 1``: weights from
the seed, the cell's traffic, warm-up, a profiler window of the traffic's
``trace_seconds``, the reference check), and its result line is printed
as that script prints it.  That run maps the decode program onto its
``ess.*`` scopes and reduces the window's trace by ``bench/scopes.py``;
this script prints the whole reduction as the second JSON line: device
milliseconds per round of each scope and its largest ops, the device's
idle gaps named by the program's host spans, and the host's own
milliseconds per round.  With ``--out`` the trace (gzipped) and the scope
maps are written there.

``--tiny`` runs the tiny CPU-test cell of ``bench/tests/tiny.py`` instead,
with a 0.1 s window (the small recorded trace under ``bench/tests/data``
was taken so).
Without a TPU it exits 1.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import run  # noqa: E402


def round_spans_us(n: int = 20000) -> float:
    """Microseconds the host spends entering and leaving the spans of one
    decode round (``ess.round`` and its six stages) with nothing inside."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    stages = ("ess.admit", "ess.plan", "ess.launch", "ess.fetch",
              "ess.commit", "ess.finish")
    t = time.perf_counter()
    for i in range(n):
        with StepTraceAnnotation("ess.round", step_num=i):
            for name in stages:
                with TraceAnnotation(name):
                    pass
    return (time.perf_counter() - t) / n * 1e6


class Capture:
    """Wraps ``run.drive_closed`` and ``run.stage_maps``: keeps the Window
    (whose ``stages`` the run fills from the trace), a copy of the
    window's trace (the run deletes its own) and the scope maps."""

    def __init__(self, drive, maps):
        self._drive, self._maps = drive, maps
        self.w = None
        self.trace = None          # path of the kept .xplane.pb
        self.maps = {}             # module name -> instruction -> scope
        self.keep = tempfile.mkdtemp(prefix="stage_trace_")

    def drive(self, eng, reqs, w, tracer, SP):
        import trace_reduce
        rec = self._drive(eng, reqs, w, tracer, SP)
        self.trace = os.path.join(self.keep, "trace.xplane.pb")
        shutil.copy(trace_reduce.newest(tracer.dir), self.trace)
        self.w = w
        return rec

    def stage_maps(self, eng):
        self.maps = self._maps(eng)
        return self.maps


def stage_report(w) -> dict:
    import scopes
    red = w.stages
    n = max(w.trace_counts.steps, 1)
    dev = red["device"]
    total = dev["total_s"]
    idle = red["idle"]
    named = sum(v for k, v in idle["by_span"].items()
                if k.startswith(scopes.PREFIX))
    return {
        "rounds": w.trace_counts.steps,
        "module_runs": dev["module_runs"],
        "scope_ms_per_round": w.scope_ms,
        "top_ops_ms_per_round": {
            k: [[nm, d / n * 1e3] for nm, d in v]
            for k, v in dev["top_ops"].items()},
        "op_ms_per_round": total / n * 1e3,
        "unscoped_share": dev["scopes"].get(scopes.UNSCOPED, 0.0)
        / total if total else None,
        "unmapped_ops": dev["unmapped_ops"],
        "unmapped_share": dev["unmapped_s"] / total if total else None,
        "idle_ms_per_round": {k: v / n * 1e3
                              for k, v in idle["by_span"].items()},
        "idle_split_ms_per_round": {k: v / n * 1e3
                                    for k, v in idle["split"].items()},
        "fetch_slack_ms": red["fetch_slack_ms"],
        "idle_named_by_ess": named / idle["idle_s"]
        if idle["idle_s"] else None,
        "longest_gaps": idle["longest"],
        "round_host_ms": red["round_host_ms"],
        "round_spans_us_off": round_spans_us(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", help="directory for the trace and the maps")
    ap.add_argument("--tiny", action="store_true",
                    help="run the tiny CPU-test cell instead")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("stage_trace: needs a TPU chip")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.tiny:
        sys.path.insert(0, os.path.join(HERE, "tests"))
        import tiny
        data = tempfile.mkdtemp(prefix="stage_tiny_")
        bench, name, root = tiny.make_tree(data), "tiny.closed", data
        # a few rounds only: the recorded trace is kept in the repository
        mix = os.path.join(data, "traffic", "tiny-closed.json")
        short = dict(run.load_json(mix), trace_seconds=0.1)
        with open(mix, "w") as f:
            json.dump(short, f)
    else:
        bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
        name, root, data = args.workload, ROOT, HERE
    cap = Capture(run.drive_closed, run.stage_maps)
    run.drive_closed, run.stage_maps = cap.drive, cap.stage_maps
    out = run.run_cell(bench, name, args.seed,
                       float(bench.get("run_seconds", 10)), True, root=root,
                       data=data)
    print(json.dumps(out), flush=True)
    rep = stage_report(cap.w)
    with jax.profiler.trace(tempfile.mkdtemp(prefix="stage_on_")):
        rep["round_spans_us_on"] = round_spans_us()
    print(json.dumps(rep), flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(cap.trace, "rb") as f, \
                gzip.open(os.path.join(args.out, "trace.xplane.pb.gz"),
                          "wb") as g:
            shutil.copyfileobj(f, g)
        # the map of each instruction that ran, not of the whole module
        import scopes
        ran = {e.name for e, _ in scopes.load(cap.trace).ops}
        with open(os.path.join(args.out, "scopes.json"), "w") as f:
            json.dump({m: {n: sc for n, sc in t.items() if n in ran}
                       for m, t in cap.maps.items()}, f, indent=0,
                      sort_keys=True)
    shutil.rmtree(cap.keep, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
