"""Rate arithmetic of the benchmark's end-to-end metrics.

Times are host ``time.perf_counter`` seconds.  A rate counts all the work
of the window over all of its time.
"""

from __future__ import annotations


def rate(count: int, t0: float, t1: float) -> float:
    """Events per second over the whole window [t0, t1]."""
    if t1 <= t0:
        raise ValueError("empty window")
    return count / (t1 - t0)
