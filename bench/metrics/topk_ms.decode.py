"""Device ms a serve round under the decode program's ``ess.topk`` scope in
the traced window: the top-``index_topk`` select over the indexer scores."""


def read(w):
    return w.scope_ms.get("ess.topk")
