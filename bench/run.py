#!/usr/bin/env python3
"""ESS serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run, all in this one process: make the weights from the seed on the
chip, build the engine (``EssEngine`` over the compiled, donated
StepPrograms), generate the cell's traffic, warm up every shape it uses,
then drive ``EssEngine.step()`` for ``--seconds`` (with ``--trace 1`` a
shorter traced span instead), then check the served tokens against the
plain reference (``bench/reference.py``) and print one JSON result as the
last line of standard output.

Everything that belongs to one configuration, traffic mix or metric is a
file that the run finds by the names in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py`` (a ``read(window)`` function) and
``bench/limits/<cell>.json`` (the limit of each number compared).

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.  The persistent compilation cache lives in
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# host tier dtype -> (bytes per latent element, bytes of the row's scale)
TIER_BYTES = {"bf16": (2, 0), "int8": (1, 2), "fp8": (1, 2)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a run measured
# ---------------------------------------------------------------------------

class Counters(dict):
    """Serve counters at one instant, read as attributes: every integer
    field of ``ServeReport`` (``rounds``, ``h2d_rows``, ...), the harness's
    own round count ``steps`` and the number of report events
    ``n_events``."""

    def __getattr__(self, name: str) -> int:
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name) from None

    def minus(self, o: "Counters") -> "Counters":
        return Counters({k: v - o.get(k, 0) for k, v in self.items()})


@dataclasses.dataclass
class Window:
    """Everything a metric reader may read about one run."""
    cell: str
    spec: dict                      # configuration file
    mix: dict                       # traffic file
    seconds: float
    t0: float = 0.0                 # window bounds (perf_counter)
    t1: float = 0.0
    setup_s: float = 0.0
    arrivals: dict = dataclasses.field(default_factory=dict)  # rid -> t
    token_times: dict = dataclasses.field(default_factory=dict)
    prompt_len: dict = dataclasses.field(default_factory=dict)
    tokens_in_window: int = 0
    start: Counters = dataclasses.field(default_factory=lambda: Counters(
        steps=0))
    end: Counters = dataclasses.field(default_factory=lambda: Counters(
        steps=0))
    # traced span (``--trace 1``)
    traced: bool = False
    busy_s: float = 0.0
    window_s: float = 0.0
    trace_counts: Counters = dataclasses.field(
        default_factory=lambda: Counters(steps=0))
    trace_lens: list = dataclasses.field(default_factory=list)
    trace_decode_ctx: list = dataclasses.field(default_factory=list)
    breakdown: dict = dataclasses.field(default_factory=dict)
    # device ms of each ess.* scope of the decode program per serve round
    # of the traced span, and the host's own ms a round (bench/scopes.py)
    scope_ms: dict = dataclasses.field(default_factory=dict)
    round_host_ms: float | None = None
    stages: dict = dataclasses.field(default_factory=dict)  # scopes.reduce
    peaks: dict = dataclasses.field(default_factory=dict)
    row_bytes: int = 0


# ---------------------------------------------------------------------------
# spec files
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, name: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the closed batch
# ---------------------------------------------------------------------------

class Recorder:
    """Collects the TokenEvents of every round."""

    def __init__(self, w: Window):
        self.w = w
        self.terminal: dict[int, tuple[str, float]] = {}
        self.prompts: dict = {}          # rid -> prompt token ids

    def take(self, events) -> None:
        w = self.w
        for ev in events:
            if ev.finish_reason is not None:
                self.terminal[ev.rid] = (ev.finish_reason, ev.t)
                continue
            w.token_times.setdefault(ev.rid, []).append(ev.t)


def counters(eng, steps: int) -> Counters:
    r = eng.session.report
    c = Counters({f.name: getattr(r, f.name) for f in dataclasses.fields(r)
                  if type(getattr(r, f.name)) is int})
    c.update(steps=steps, n_events=len(r.events))
    return c


def row_bytes(spec: dict, dtype: str) -> int:
    """Bytes of one latent row in the host tier: ``kv_lora_rank +
    qk_rope_head_dim`` elements, and a row scale on a quantized tier."""
    elem, scale = TIER_BYTES[dtype]
    return elem * (spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) + scale


def stage_maps(eng) -> dict[str, dict[str, str]]:
    """``{module: {instruction: ess.* scope}}`` of the round program the
    engine decodes with, from its optimized HLO (lowered and compiled
    again on the same arguments: a compile-cache hit)."""
    from repro.analysis.hlo_scopes import op_scopes
    s = eng.session
    fn = s._programs.spec(True) if s.mtp_depth > 0 \
        else s._programs.decode(True)
    t = time.perf_counter()
    mod, table = op_scopes(fn.lower(s.params, s.state).compile().as_text())
    log(f"scopes: {mod} mapped ({len(table)} instructions) in "
        f"{time.perf_counter() - t:.2f}s")
    return {mod: table}


def read_trace(w: Window, path: str, maps: dict) -> None:
    """Put the device numbers of the trace at ``path`` on ``w``: busy and
    window seconds, the top ops and idle gaps, and, on one device, each
    scope's ms a serve round and the host's own ms a round."""
    import scopes
    import trace_reduce as tr
    red = tr.reduce_events(*tr.load(path))
    w.busy_s, w.window_s = red.busy_s, red.window_s
    w.breakdown = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    if red.n_devices != 1:
        log(f"scopes: not read on {red.n_devices} devices")
        return
    module = next(iter(maps), scopes.DECODE_MODULE)
    w.stages = scopes.reduce(scopes.load(path), maps, module)
    n = w.trace_counts.steps
    if n:
        w.scope_ms = {k: v / n * 1e3
                      for k, v in w.stages["device"]["scopes"].items()}
        w.breakdown["scopes"] = sorted(
            ([k, v] for k, v in w.scope_ms.items()),
            key=lambda kv: -kv[1])[:10]
    w.round_host_ms = w.stages["round_host_ms"]


class Tracer:
    """Profiler span inside the window (``--trace 1``)."""

    def __init__(self, on: bool, seconds: float):
        self.on = on
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.active = False
        self.t_stop = math.inf
        self.ann = None

    def start(self, t: float) -> None:
        import jax
        if not self.on:
            return
        jax.profiler.start_trace(self.dir)
        self.ann = jax.profiler.TraceAnnotation("bench.window")
        self.ann.__enter__()
        self.active = True
        self.t_stop = t + self.seconds

    def stop(self) -> None:
        import jax
        if self.active:
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive_closed(eng, reqs, w: Window, tracer: Tracer, SP) -> Recorder:
    """Offline batch: submit all, fill every slot (set-up), run
    ``warm_rounds`` more rounds, then measure decode rounds."""
    rec = Recorder(w)
    rids = []
    for r in reqs:
        rid = eng.submit(r.prompt, SP(max_tokens=r.max_tokens))
        rids.append(rid)
        rec.prompts[rid] = r.prompt
        w.prompt_len[rid] = r.prompt_len
    total = sum(r.prompt_len for r in reqs)
    steps = 0
    while eng.session.report.prefill_tokens < total:
        rec.take(eng.step())
        steps += 1
    for _ in range(int(w.mix.get("warm_rounds", 0))):
        rec.take(eng.step())
        steps += 1
    if rec.terminal:
        raise RuntimeError(f"requests ended during set-up: {rec.terminal}")
    w.setup_s = time.perf_counter() - T_START
    w.t0 = time.perf_counter()
    w.arrivals = {rid: w.t0 for rid in rids}
    w.start = counters(eng, steps)
    end = w.t0 + w.seconds
    if tracer.on:
        tracer.start(w.t0)
        tr0 = counters(eng, steps)
        w.trace_lens = [w.prompt_len[r] + len(eng.session.outputs[r])
                        for r in rids]
        end = tracer.t_stop
    while time.perf_counter() < end:
        with span("bench.step"):
            rec.take(eng.step())
        steps += 1
    w.t1 = time.perf_counter()
    w.end = counters(eng, steps)
    if tracer.on:
        tracer.stop()
        w.trace_counts = w.end.minus(tr0)
        w.trace_decode_ctx = [n + k for n in w.trace_lens
                              for k in range(w.trace_counts.steps)]
    if rec.terminal:
        raise RuntimeError(f"requests ended inside the window: "
                           f"{rec.terminal}")
    return rec


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def pick_checked(w: Window, outputs: dict, n: int, seed: int) -> list[int]:
    """``n`` requests to compare, drawn from the seed, the longest (most
    prompt plus served tokens) among them."""
    import numpy as np
    pool = sorted(w.arrivals)
    if not pool:
        raise RuntimeError("no served request to compare")
    size = {r: w.prompt_len[r] + len(outputs[r]) for r in pool}
    top = max(size.values())
    order = np.random.default_rng([seed % 2 ** 64, 3]).permutation(pool)
    longest = next(int(r) for r in order if size[r] == top)
    return [longest] + [int(r) for r in order if r != longest][:n - 1]


def check(spec, params, max_len, prompts, outputs, picked, *, control):
    """Widest ``served_gap`` over the ``picked`` requests.  With
    ``control`` the float8 control's own first choices stand in the served
    tokens' place (teacher-forced on the served tokens), and the program's
    gap is returned beside it as ``program_gap``."""
    import reference
    worst = {"served_gap": 0.0, "tokens": 0}
    ref = reference.Reference(spec, params, max_len)
    ctl = None
    if control:
        worst["program_gap"] = 0.0
        ctl = reference.Reference(spec, params, max_len, lowp=True)
    for rid in picked:
        g = reference.served_gaps(ref, prompts[rid], outputs[rid],
                                  control=ctl)
        worst["tokens"] += g["n"]
        for k in worst:
            if k != "tokens":
                worst[k] = max(worst[k], g[k])
    return worst


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             *, control: bool = False, require_tpu: bool = True,
             root: str = ROOT, data: str = HERE) -> dict:
    """One run of cell ``name``.  Configuration files resolve against
    ``root``; traffic and limit files live under ``data``."""
    import jax
    sys.path.insert(0, HERE)
    import config_map
    import flops
    import traffic
    import weights

    dev = jax.devices()[0]
    cell = find_cell(bench, name)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    spec = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    limits_path = os.path.join(data, "limits", name + ".json")
    limits = load_json(limits_path) if os.path.exists(limits_path) else {}
    if require_tpu:
        peaks = flops.peaks(os.path.join(HERE, "peaks.json"), dev.device_kind)
    else:
        peaks = {}

    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.serving.api import EssEngine, SamplingParams

    mapped = config_map.to_program(spec)
    cfg = mapped.cfg
    log(f"config {cell['config']}: not taken by the program: "
        f"{', '.join(mapped.not_taken) or 'none'}")
    w = Window(cell=name, spec=spec, mix=mix,
               seconds=float(seconds), traced=trace, peaks=peaks,
               row_bytes=row_bytes(spec, cfg.ess.host_cache_dtype))
    params = weights.make(abstract_params(T.model_def(cfg)), seed,
                          config_map.arch_module(spec))
    jax.block_until_ready(params)
    log(f"weights ready at {time.perf_counter() - T_START:.2f}s")
    reqs = traffic.generate(mix, seed, cfg.vocab_size)
    e = mix["engine"]
    eng = EssEngine(params, cfg, num_slots=int(e["num_slots"]),
                    max_seq=int(e["max_seq"]),
                    prefill_chunk=int(e["prefill_chunk"]),
                    mtp_depth=mapped.mtp_depth)
    tracer = Tracer(trace, float(mix.get("trace_seconds", seconds)))
    rec = drive_closed(eng, reqs, w, tracer, SamplingParams)
    maps = stage_maps(eng) if trace else {}
    w.tokens_in_window = sum(
        1 for ts in w.token_times.values() for t in ts if w.t0 <= t <= w.t1)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    outputs = {rid: list(v) for rid, v in eng.session.outputs.items()}
    rounds = w.end.minus(w.start)
    log(f"window {w.t1 - w.t0:.3f}s: {rounds.steps} rounds, "
        f"{w.tokens_in_window} tokens, {rounds.prefill_chunks} prefill "
        f"chunks, {rounds.h2d_rows} miss rows, {len(w.arrivals)} requests")
    del eng
    gc.collect()

    if trace:
        import trace_reduce as tr
        read_trace(w, tr.newest(tracer.dir), maps)

    metrics = {}
    for m in cell_metrics(bench, name, trace):
        v = load_reader(m["name"])(w)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace:
        shutil.rmtree(tracer.dir, ignore_errors=True)

    # correctness: the served tokens (with --control 1 the control's)
    # against the plain reference
    picked = pick_checked(w, outputs, int(mix.get("check_requests", 1)), seed)
    t_chk = time.perf_counter()
    got = check(spec, params, int(e["max_seq"]), rec.prompts, outputs, picked,
                control=control)
    log(f"reference over requests {picked}: {got['tokens']} served tokens "
        f"in {time.perf_counter() - t_chk:.2f}s")
    checks = {}
    correct = True
    for key in ("served_gap",):
        lim = limits.get(key, {}).get("limit")
        checks[key] = {"value": got[key], "limit": lim}
        correct = correct and lim is not None and got[key] <= lim
    if control:
        log(f"control run: the program's own served_gap "
            f"{got['program_gap']!r} (not compared)")
    # a request that ends before the window closes stops the run
    out = {"correct": bool(correct), "attempted": len(w.arrivals),
           "failed": 0, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if trace:
        out["device"]["busy_s"] = w.busy_s
        out["device"]["window_s"] = w.window_s
        out["breakdown"] = w.breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the float8 control's first choices in the "
                    "served tokens' place (a run that must come out not "
                    "correct)")
    args = ap.parse_args(argv)

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("bench: the program (src/repro) is not in this checkout")
        return 2
    bench = load_json(bench_path)
    find_cell(bench, args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    devs = jax.devices()
    need = find_cell(bench, args.workload)["chips"]
    if devs[0].platform != "tpu" or len(devs) < need:
        log(f"bench: needs {need} TPU chip(s), JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s)")
        return 1
    from repro.kernels.common import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    if default_interpret():
        log("bench: Pallas would run in interpret mode")
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), control=bool(args.control))
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
