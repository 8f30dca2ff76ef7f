"""Model step, paged decode: share of the KV positions the gather path
would gather that the window's decode steps read, summed over the
``serve.decode`` spans (``kv_read`` over ``kv_gathered``). On the paged
decode kernel ``kv_read`` counts the whole lane tiles of 128 pages that hold
a page a live row attends to; on the gather path it equals ``kv_gathered``.
A program whose spans carry no ``kv_read`` reads None."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    dec = spans.events("serve.decode")
    if not dec or any("kv_read" not in e.stats for e in dec):
        return None
    gathered = sum(e.stats["kv_gathered"] for e in dec)
    return 100.0 * sum(e.stats["kv_read"] for e in dec) / gathered \
        if gathered else None
