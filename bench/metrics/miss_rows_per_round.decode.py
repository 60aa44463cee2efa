"""Latent rows the cache tier gathered for pool misses, per decode round
(``ServeReport.h2d_rows``), over the traced window."""


def read(w):
    n = w.trace_counts.rounds
    if not w.traced or n == 0:
        return None
    return w.trace_counts.h2d_rows / n
