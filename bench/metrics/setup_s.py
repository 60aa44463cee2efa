"""Set-up time: process start to window start (weights, compiles or cache
loads, warm-up, and the cache fill of a closed batch)."""


def read(w):
    return w.setup_s
