"""Scheduler: host time of one batcher step, the mean over the window's
``serve.step`` spans of each step's time less its device waits (the
``serve.readback`` of the decode's tokens and the ``serve.first_token``
read of each admission): admission, page bookkeeping, block tables and
dispatch."""


def read(run):
    s = run.trace.spans if run.trace is not None else {}
    steps = s.get("serve.step")
    if not steps:
        return None
    waits = sum(s.get("serve.readback", ())) \
        + sum(s.get("serve.first_token", ()))
    return (sum(steps) - waits) / len(steps) * 1e3
