"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The harness annotates the traced window with host spans
(``jax.profiler.TraceAnnotation``): ``bench.window`` around the whole
traced span, and inside it one span per thing the host does (``bench.step``
for a serve round).  The device planes (``/device:TPU:n``) carry one event
per operation that ran, on their ``XLA Ops`` line.

From these: the busy time (union of operation intervals, averaged over the
chips), the window length, the operations that took most time, and the
longest stretches inside the window where no operation ran, each named by
the host span that covered most of it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_LAUNCH = "PJRT_LoadedExecutable_Execute"
DEVICE_PREFIX = "/device:TPU:"    # not /device:CUSTOM:..., not the host
WINDOW_SPAN = "bench.window"


class Interval(NamedTuple):
    name: str
    start: float      # ns
    end: float        # ns


class Reduced(NamedTuple):
    busy_s: float                 # union of op intervals, mean over chips
    window_s: float               # length of the traced window
    device_ops: list              # [[name, seconds], ...] most time first
    idle_gaps: list               # [[host span, seconds], ...] longest first
    n_devices: int


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(busy: list[tuple[float, float]], w0: float, w1: float
              ) -> list[tuple[float, float]]:
    """Stretches of [w0, w1] that no busy interval covers."""
    gaps, t = [], w0
    for s, e in sorted(busy):
        if e <= t:
            continue
        if s > t:
            gaps.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        gaps.append((t, w1))
    return [(a, b) for a, b in gaps if b > a]


def _clip(iv: list[tuple[float, float]], w0: float, w1: float):
    return [(max(s, w0), min(e, w1)) for s, e in iv if e > w0 and s < w1]


def name_gap(gap: tuple[float, float], spans: list[Interval]) -> str:
    """The host span that overlaps the gap the most (``host`` if none)."""
    best, name = 0.0, "host"
    for sp in spans:
        ov = min(gap[1], sp.end) - max(gap[0], sp.start)
        if ov > best:
            best, name = ov, sp.name
    return name


def reduce_events(device_ops: dict[str, list[Interval]],
                  host_spans: list[Interval], top: int = 10) -> Reduced:
    """``device_ops`` maps a device plane to its op events; ``host_spans``
    are the harness's annotations (one of them ``bench.window``)."""
    wins = [s for s in host_spans if s.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got {len(wins)}")
    w0, w1 = wins[0].start, wins[0].end
    inner = [s for s in host_spans if s.name != WINDOW_SPAN]
    if not device_ops:
        raise ValueError("no device plane in the trace")
    busy_total = 0.0
    per_op: dict[str, float] = defaultdict(float)
    gap_time: dict[str, float] = defaultdict(float)
    longest: list[tuple[float, str]] = []
    for evs in device_ops.values():
        iv = _clip([(e.start, e.end) for e in evs], w0, w1)
        busy_total += union_length(iv)
        for e in evs:
            d = min(e.end, w1) - max(e.start, w0)
            if d > 0:
                per_op[e.name] += d
        for g in idle_gaps(iv, w0, w1):
            nm = name_gap(g, inner)
            gap_time[nm] += g[1] - g[0]
            longest.append((g[1] - g[0], nm))
    n = len(device_ops)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    longest.sort(key=lambda x: -x[0])
    return Reduced(
        busy_s=busy_total / n / 1e9,
        window_s=(w1 - w0) / 1e9,
        device_ops=[[k, v / n / 1e9] for k, v in ops],
        idle_gaps=[[nm, d / 1e9] for d, nm in longest[:top]],
        n_devices=n)


def clock_offset(modules: list[float], launches: list[float]) -> float:
    """Nanoseconds to add to a device plane's times so that no program
    starts on the device before the host call that launched it.  The
    k-th program on the device is the k-th launch on the host; where the
    counts differ the planes are left as they are."""
    if not modules or len(modules) != len(launches):
        return 0.0
    return max(0.0, max(h - m for h, m in zip(sorted(launches),
                                               sorted(modules))))


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def newest(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir`` (or that file
    itself)."""
    paths = [trace_dir] if trace_dir.endswith(".xplane.pb") else glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str) -> tuple[dict[str, list[Interval]], list[Interval]]:
    """Device op events and host ``bench.*`` spans of the newest
    ``.xplane.pb`` under ``trace_dir`` (or of that file itself)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(newest(trace_dir))
    dev: dict[str, list[Interval]] = {}
    modules: dict[str, list[float]] = {}
    host: list[Interval] = []
    launches: list[float] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(Interval(op_name(e.name), e.start_ns,
                                        e.end_ns) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [e.start_ns for e in line.events]
            dev[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Interval(e.name, e.start_ns, e.end_ns))
                    elif e.name == HOST_LAUNCH:
                        launches.append(e.start_ns)
    for name, evs in dev.items():
        off = clock_offset(modules.get(name, []), launches) \
            if len(dev) == 1 else 0.0
        dev[name] = [Interval(e.name, e.start + off, e.end + off)
                     for e in evs]
    return dev, host
