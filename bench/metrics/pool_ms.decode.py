"""Device ms a serve round under the decode program's ``ess.pool`` scope in
the traced window: the cache tier resolves the selected ids against the
device LRU pool and admits and evicts its entries."""


def read(w):
    return w.scope_ms.get("ess.pool")
