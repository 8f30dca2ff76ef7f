"""Scheduler: p95 over the window's first admissions of the wait the
batcher itself records, from ``submit`` to the start of the admission
(``wait_us`` on its ``serve.admit`` spans; a re-admission after preemption
carries none). ``queue_wait_p95_ms.serve`` times from each request's due
time to the start of the step that admitted it: the difference is the
step under way when the request came."""
from bench import spans
from bench.common import p95


def read(run):
    if run.trace is None:
        return None
    waits = [e.stats["wait_us"] for e in spans.events("serve.admit")
             if "wait_us" in e.stats]
    return p95(waits) / 1e3 if waits else None
