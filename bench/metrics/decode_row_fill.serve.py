"""Scheduler: share of the decode rows computed in the window that held a
live request, summed over the ``serve.decode`` spans (``live`` over
``rows``): the rest is the live-slot bucket's padding."""
from bench import spans


def read(run):
    if run.trace is None:
        return None
    dec = spans.events("serve.decode")
    rows = sum(e.stats["rows"] for e in dec)
    return 100.0 * sum(e.stats["live"] for e in dec) / rows if rows else None
