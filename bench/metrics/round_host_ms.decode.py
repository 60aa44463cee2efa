"""The host's own ms a serve round in the traced window: each ``ess.round``
span less its ``ess.fetch`` child (the wait for the device), averaged over
the rounds (bench/scopes.py ``round_host_ms``)."""


def read(w):
    return w.round_host_ms
