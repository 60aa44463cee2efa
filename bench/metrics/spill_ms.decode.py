"""Device ms a serve round under the decode program's ``ess.spill`` scope
in the traced window: the cache tier writes the round's new latent rows
back to the host tier."""


def read(w):
    return w.scope_ms.get("ess.spill")
