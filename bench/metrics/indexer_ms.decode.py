"""Device ms a serve round under the decode program's ``ess.indexer``
scope in the traced window: the DSA indexer appends the round's keys and
scores every live position."""


def read(w):
    return w.scope_ms.get("ess.indexer")
