"""Device ms a serve round under the decode program's ``ess.ffn`` scope in
the traced window: the dense and MoE feed-forward layers."""


def read(w):
    return w.scope_ms.get("ess.ffn")
