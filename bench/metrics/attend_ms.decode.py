"""Device ms a serve round under the decode program's ``ess.attend`` scope
in the traced window: absorbed MLA over the selected latent rows."""


def read(w):
    return w.scope_ms.get("ess.attend")
