"""Model step, paged prefill: mean device time of one prefill program
execution in the traced window (all prompt buckets together)."""

# the batcher jits its paged prefill from a lambda
PROGRAMS = ("_lambda",)


def read(run):
    mean = run.trace.module_mean(PROGRAMS) if run.trace else None
    return mean * 1e3 if mean is not None else None
