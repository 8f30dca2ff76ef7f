"""Model step, paged decode: the least time one decode step could take on
the chip over the mean device time of a decode program execution. The
least time is the larger of its operations at the bf16 peak and its bytes
(every weight once and each live row's keys and values once) at the HBM
bandwidth, both from shapes (``bench/flops.py``) and averaged over the
window's decode steps; at these sizes the bytes bound it."""

PROGRAMS = ("decode",)


def read(run):
    c = run.counters
    mean = run.trace.module_mean(PROGRAMS) if run.trace else None
    if mean is None or not c.get("decode_steps"):
        return None
    n = c["decode_steps"]
    least = max(c["decode_bytes"] / n / run.peaks["hbm_bytes_per_s"],
                c["decode_flops"] / n / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / mean
