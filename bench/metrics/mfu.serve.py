"""Device, whole step: model FLOPs of the prompt and decode tokens the
window's ``step`` calls processed (``bench/flops.py``; no padding, logits
only where used) over the summed wall time of those calls, as a share of
the chip's bf16 peak. It follows step speed, not the offered rate."""


def read(run):
    c = run.counters
    if not c.get("step_wall_s") or not c.get("model_flops"):
        return None
    return 100.0 * c["model_flops"] / c["step_wall_s"] \
        / run.peaks["bf16_flops_per_s"]
