"""Least time of one decode round on this chip -- the larger of its model
FLOPs over the peak rate and its least bytes over the HBM bandwidth --
over the measured device-busy time per round.  Prints which bound
applies."""
import sys

import flops


def read(w):
    n = w.trace_counts.steps
    if not w.traced or n == 0 or w.busy_s <= 0 or not w.trace_lens:
        return None
    m = flops.Model(w.spec)
    f = sum(m.token_flops(x) for x in w.trace_lens)
    b = m.decode_round_bytes(w.trace_lens, w.row_bytes)
    t_f = f / w.peaks["bf16_flops_per_s"]
    t_b = b / w.peaks["hbm_bytes_per_s"]
    print(f"decode_roofline: {'bytes' if t_b >= t_f else 'flops'} bound "
          f"({t_b * 1e3:.4f} ms for {b:.4e} B, {t_f * 1e3:.4f} ms for "
          f"{f:.4e} FLOP per round)", file=sys.stderr)
    return max(t_f, t_b) / (w.busy_s / n) * 100.0
