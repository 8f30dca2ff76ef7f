"""Model step, paged decode: share of the KV positions the window's decode
steps gathered that a live row holds, summed over the ``serve.decode``
spans (``kv_live``, each live row's position + 1, over ``kv_gathered``,
rows x blocks per row x page size)."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    dec = spans.events("serve.decode")
    gathered = sum(e.stats["kv_gathered"] for e in dec)
    return 100.0 * sum(e.stats["kv_live"] for e in dec) / gathered \
        if gathered else None
