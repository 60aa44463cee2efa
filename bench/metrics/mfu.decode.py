"""Model FLOPs of the decode tokens of the traced window over the device's
busy seconds times the chip's peak (bench/flops.py, bench/peaks.json)."""
import flops


def read(w):
    if not w.traced or w.busy_s <= 0 or not w.trace_decode_ctx:
        return None
    m = flops.Model(w.spec)
    f = sum(m.token_flops(n) for n in w.trace_decode_ctx)
    return f / (w.busy_s * w.peaks["bf16_flops_per_s"]) * 100.0
