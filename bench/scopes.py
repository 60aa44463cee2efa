"""Stage names of the ESS serve round in a JAX profiler trace.

The program names each stage of a round on both sides of the chip:

* device: every op of a round program lies under one ``jax.named_scope``
  called ``ess.<stage>`` (``ess.indexer``, ``ess.topk``, ...).  XLA keeps
  the scope in each instruction's ``metadata={op_name=...}``; a fusion
  carries the metadata of its root.  The trace's op events name the
  instruction only (``fusion.666``), so the scopes are read from the
  optimized HLO of the program that ran (``compiled.as_text()``, mapped
  by ``repro.analysis.hlo_scopes.op_scopes``).
* host: ``ess.round`` (a ``StepTraceAnnotation``) around each serve round,
  and inside it ``ess.admit``, ``ess.prefill``, ``ess.plan``,
  ``ess.launch``, ``ess.fetch``, ``ess.commit``, ``ess.finish``.

From a trace and the instruction maps of its modules: the device seconds
of each scope, and each idle gap of the device named by the innermost
host span that covers most of it.  Device times are shifted onto the host
clock as ``trace_reduce.load`` shifts them.  ``bench/stage_trace.py``
runs a cell and prints this reduction of its traced window.
"""

from __future__ import annotations

import bisect
import gzip
import heapq
from collections import defaultdict
from typing import NamedTuple

import trace_reduce as tr
from trace_reduce import Interval

from repro.analysis.contracts import ROUND_MODULES, ROUND_SPAN
from repro.analysis.hlo_scopes import PREFIX, UNSCOPED, op_scopes  # noqa: F401

DECODE_MODULE = ROUND_MODULES["decode"]
FETCH_SPAN = "ess.fetch"


def module_name(event_name: str) -> str:
    """``jit_decode_round(1234)`` -> ``jit_decode_round``."""
    return event_name.split("(", 1)[0]


class Trace(NamedTuple):
    ops: list          # [(Interval, module name)] on the host clock
    modules: list      # [Interval] of module runs on the host clock
    spans: list        # [Interval] of bench.* and ess.* host spans


def load(path: str) -> Trace:
    """Device ops with the module each ran in, and the host spans, of one
    ``.xplane.pb`` (or ``.xplane.pb.gz``) file of a one-device trace."""
    from jax.profiler import ProfileData
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    dev_ops, dev_mods, spans, launches = [], [], [], []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            n_dev += 1
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    dev_ops.extend(Interval(tr.op_name(e.name), e.start_ns,
                                            e.end_ns) for e in line.events)
                elif line.name == tr.MODULES_LINE:
                    dev_mods.extend(Interval(module_name(e.name), e.start_ns,
                                             e.end_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split("#", 1)[0]
                    if name.startswith(("bench.", PREFIX)):
                        spans.append(Interval(name, e.start_ns, e.end_ns))
                    elif name == tr.HOST_LAUNCH:
                        launches.append(e.start_ns)
    if n_dev != 1:
        raise ValueError(f"expected one device plane, got {n_dev}")
    off = tr.clock_offset([m.start for m in dev_mods], launches)
    mods = sorted(Interval(m.name, m.start + off, m.end + off)
                  for m in dev_mods)
    starts = [m.start for m in mods]
    ops = []
    for e in dev_ops:
        s, t = e.start + off, e.end + off
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i].name if i >= 0 and s < mods[i].end else ""
        ops.append((Interval(e.name, s, t), mod))
    return Trace(ops, mods, spans)


def window_of(spans: list[Interval]) -> tuple[float, float]:
    wins = [s for s in spans if s.name == tr.WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {tr.WINDOW_SPAN} span, "
                         f"got {len(wins)}")
    return wins[0].start, wins[0].end


def self_times(ops: list[tuple[float, float, float]]) -> list[float]:
    """Each op's own share of the instants that ``ops`` (``(start, end,
    length)``, clipped to a window, with the op's unclipped length)
    cover: an instant that several ops cover goes to the shortest of them,
    so that a ``while`` or ``conditional`` op, whose event spans the ops of
    its body, keeps only the time between them.  The shares add up to the
    union of the ops."""
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(ops)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(ops)])
    own = [0.0] * len(ops)
    active: list[tuple[float, int]] = []       # heap of (length, op)
    ended: set[int] = set()
    prev = None
    for t, opens, i in edges:
        while active and active[0][1] in ended:
            heapq.heappop(active)
        if active and prev is not None:
            own[active[0][1]] += t - prev
        prev = t
        if opens:
            heapq.heappush(active, (ops[i][2], i))
        else:
            ended.add(i)
    return own


def scope_seconds(trace: Trace, maps: dict[str, dict[str, str]],
                  module: str = DECODE_MODULE, top: int = 4) -> dict:
    """Device seconds of each scope over the ops of ``module`` inside the
    window, each instant counted once (:func:`self_times`), with
    ``unmapped`` (ops whose name the module's map lacks) counted apart,
    the total, and each scope's ``top`` ops by seconds."""
    w0, w1 = window_of(trace.spans)
    table = maps.get(module, {})
    names, clipped = [], []
    for e, mod in trace.ops:
        s, t = max(e.start, w0), min(e.end, w1)
        if mod == module and t > s:
            names.append(e.name)
            clipped.append((s, t, e.end - e.start))
    sec: dict[str, float] = defaultdict(float)
    per_op: dict[str, float] = defaultdict(float)
    unmapped_n, unmapped_s = 0, 0.0
    for name, d in zip(names, self_times(clipped)):
        sc = table.get(name)
        if sc is None:
            unmapped_n += 1
            unmapped_s += d
        else:
            sec[sc] += d
            per_op[name] += d
    ops: dict[str, list] = defaultdict(list)
    for name, d in sorted(per_op.items(), key=lambda kv: -kv[1]):
        if len(ops[table[name]]) < top:
            ops[table[name]].append([name, d / 1e9])
    return {"scopes": {k: v / 1e9 for k, v in sorted(sec.items())},
            "top_ops": dict(sorted(ops.items())),
            "unmapped_ops": unmapped_n, "unmapped_s": unmapped_s / 1e9,
            "total_s": sum(sec.values(), unmapped_s) / 1e9,
            "module_runs": sum(1 for m in trace.modules
                               if m.name == module and w0 <= m.start < w1)}


def name_gap(gap: tuple[float, float], spans: list[Interval]) -> str:
    """The innermost (shortest) host span that covers more than half of
    the gap; failing that the span that covers most of it (``host`` if
    none does)."""
    g = gap[1] - gap[0]
    best, most, inner = 0.0, "host", None
    for sp in spans:
        ov = min(gap[1], sp.end) - max(gap[0], sp.start)
        if ov <= 0:
            continue
        if ov > best:
            best, most = ov, sp.name
        if 2 * ov > g and (inner is None
                           or sp.end - sp.start < inner.end - inner.start):
            inner = sp
    return inner.name if inner is not None else most


def split_gap(gap: tuple[float, float], spans: list[Interval]
              ) -> dict[str, float]:
    """The gap cut at every span edge inside it, each piece given to the
    innermost span that covers it (``host`` where none does)."""
    cover = [sp for sp in spans if sp.start < gap[1] and sp.end > gap[0]]
    edges = sorted({gap[0], gap[1]} | {t for sp in cover
                                       for t in (sp.start, sp.end)
                                       if gap[0] < t < gap[1]})
    out: dict[str, float] = defaultdict(float)
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        inner = min((sp for sp in cover if sp.start <= mid < sp.end),
                    key=lambda sp: sp.end - sp.start, default=None)
        out[inner.name if inner is not None else "host"] += b - a
    return out


def idle_gaps(trace: Trace, top: int = 10) -> dict:
    """The device's idle stretches inside the window, each named by
    :func:`name_gap`: the seconds per name and the ``top`` longest; and
    the idle seconds under each innermost span (:func:`split_gap`)."""
    w0, w1 = window_of(trace.spans)
    inner = [s for s in trace.spans if s.name != tr.WINDOW_SPAN]
    busy = tr._clip([(e.start, e.end) for e, _ in trace.ops], w0, w1)
    per: dict[str, float] = defaultdict(float)
    split: dict[str, float] = defaultdict(float)
    longest = []
    for g in tr.idle_gaps(busy, w0, w1):
        nm = name_gap(g, inner)
        per[nm] += g[1] - g[0]
        longest.append((g[1] - g[0], nm))
        for k, v in split_gap(g, inner).items():
            split[k] += v
    longest.sort(key=lambda x: -x[0])

    def by_time(d):
        return {k: v / 1e9 for k, v in sorted(d.items(),
                                             key=lambda kv: -kv[1])}
    return {"by_span": by_time(per), "split": by_time(split),
            "longest": [[nm, d / 1e9] for d, nm in longest[:top]],
            "idle_s": sum(per.values()) / 1e9}


def fetch_slack_ms(trace: Trace, module: str = DECODE_MODULE
                   ) -> float | None:
    """How much later the device clock could sit than ``load`` puts it:
    the least time, over the runs of ``module``, from a run's end to the
    end of the first ``ess.fetch`` that returns after it (a fetch cannot
    return before the program it waits for has ended).  Near zero, the
    device and host clocks are pinned to each other; otherwise idle time
    may lie up to this much later than the reduction places it."""
    ends = sorted(s.end for s in trace.spans if s.name == FETCH_SPAN)
    best = None
    for m in trace.modules:
        if m.name != module:
            continue
        i = bisect.bisect_left(ends, m.end)
        if i < len(ends):
            d = ends[i] - m.end
            best = d if best is None else min(best, d)
    return None if best is None else best / 1e6


def round_host_ms(trace: Trace) -> float | None:
    """Mean over the ``ess.round`` spans inside the window of the round's
    length less its ``ess.fetch`` child: the host's own time per round."""
    w0, w1 = window_of(trace.spans)
    rounds = [s for s in trace.spans
              if s.name == ROUND_SPAN and w0 <= s.start and s.end <= w1]
    if not rounds:
        return None
    fetches = sorted((s.start, s.end) for s in trace.spans
                     if s.name == FETCH_SPAN)
    starts = [f[0] for f in fetches]
    tot = 0.0
    for r in rounds:
        own = r.end - r.start
        i = bisect.bisect_left(starts, r.start)
        while i < len(fetches) and fetches[i][0] < r.end:
            own -= min(fetches[i][1], r.end) - fetches[i][0]
            i += 1
        tot += own
    return tot / len(rounds) / 1e6


def reduce(trace: Trace, maps: dict, module: str = DECODE_MODULE) -> dict:
    """The three readings above of one trace."""
    return {"device": scope_seconds(trace, maps, module),
            "idle": idle_gaps(trace), "round_host_ms": round_host_ms(trace),
            "fetch_slack_ms": fetch_slack_ms(trace, module)}
