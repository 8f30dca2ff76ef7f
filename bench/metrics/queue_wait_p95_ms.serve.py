"""Scheduler: p95 over the window's requests of the wait from when each was
due to the start of the ``step`` that admitted it; requests never admitted
count at their age when the window closed. Harness clock."""
from bench.common import p95


def read(run):
    waits = run.counters.get("queue_wait_s")
    return p95(waits) * 1e3 if waits else None
