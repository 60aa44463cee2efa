"""Device-busy time per serve round in the traced window.  Sound where the
window holds decode rounds alone (a closed batch after its fill)."""


def read(w):
    n = w.trace_counts.steps
    if not w.traced or n == 0 or w.trace_counts.prefill_chunks:
        return None
    return w.busy_s / n * 1e3
