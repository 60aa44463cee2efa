"""Device ms a serve round under the decode program's ``ess.head`` scope in
the traced window: the final norm, the output head and the token
selection."""


def read(w):
    return w.scope_ms.get("ess.head")
