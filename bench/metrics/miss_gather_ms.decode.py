"""Device ms a serve round under the decode program's ``ess.miss_gather``
scope in the traced window: the cache tier gathers the selected rows the
device pool lacks from the host tier (dequantized on an int8 or fp8 tier)."""


def read(w):
    return w.scope_ms.get("ess.miss_gather")
