"""Knee sweep of an open-loop serving cell: the highest offered rate whose
backlog does not grow over a window. Run once, on the chip, to fix the
rate a mix file states; the benchmark's own runs never search for a rate.

    python3 bench/sweep.py --workload serve-chat --seed 7 --seconds 150 \\
        --rates 0.65,0.72,0.8,0.88

Each rate is one run of the cell as ``bench/run.py`` makes it (the same
``execute``, in one process), with the mix's rate replaced: set-up, the
mix's untimed pre-warm, then a window of ``--seconds``, which should span
several service times so that a backlog that grows shows. One JSON line
per rate: the end-to-end values, requests offered and finished in the
window, the queue at the window's start and end, the mean live slots and
pool share, and the queue and live slots every ten seconds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# run as a script, this directory heads the path: the checkout takes its
# place, so that bench/trace.py cannot stand in for the standard library's
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path[0] = str(ROOT)
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402
from bench import run as harness  # noqa: E402

KEEP = ("offered", "finished", "backlog_start", "backlog_end", "live_mean",
        "pool_share_mean", "pool_share_max", "preempted", "steps",
        "step_wall_s", "decode_tokens", "late_s", "ttft_p50_ms",
        "itl_p50_ms", "checked_tokens")
EVERY_S = 10.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    import jax
    common.use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from bench.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind)
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        began = time.perf_counter()
        at_rate = dataclasses.replace(cell,
                                      mix=dict(cell.mix, rate_per_s=rate))
        out, run, _ = harness.execute(at_rate, args.seed + n, args.seconds,
                                      False, jax, devices[:cell.chips], peaks)
        c = run.counters
        marks, nxt = [], 0.0
        for t, queued, live, _pages in c["occupancy"]:
            if t >= nxt:
                marks.append((t, queued, live))
                nxt += EVERY_S
        print(json.dumps(dict(
            {"rate_per_s": rate, "seed": args.seed + n,
             "correct": out["correct"],
             "served_mean_gap": out["checks"]["served_mean_gap"]["value"]},
            **run.e2e, **{k: c[k] for k in KEEP},
            queued_live_every=marks,
            wall_s=time.perf_counter() - began)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
