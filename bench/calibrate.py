"""Readings that a serving cell's correctness limit is set from. Run on the
chip at the cell's own size; the benchmark's own runs never run it.

    python3 bench/calibrate.py --workload serve-chat --seeds 101-112 \\
        --control 3 --seconds 51

Each seed is one run of the cell as ``bench/run.py`` makes it (the same
``execute``, in one process): the program's reading is the number the run
compares. On the first ``--control`` seeds the run puts the control in the
program's place: the reference computed in float8 e4m3, at every position
of the same prompts and served tokens, read as the gap of the token it
puts first; the program's reading on that sample is printed beside it. One
JSON line per seed, each reading with its mean gap (what a run compares),
widest gap, 99th percentile and share of tokens that are not the
reference's greedy choice. The lower reading is the largest program
reading, the upper the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
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


def seeds_arg(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    import jax
    common.use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from bench.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind)
    for n, seed in enumerate(args.seeds):
        control = "fp8" if n < args.control else None
        out, run, _ = harness.execute(cell, seed, args.seconds, False, jax,
                                      devices[:cell.chips], peaks,
                                      control=control)
        c = run.counters
        row = {"seed": seed, "requests": c["checked_requests"],
               "tokens": c["checked_tokens"], "program": c["program_gap"],
               "correct": out["correct"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if control:
            row["control_" + control] = c["control_gap"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
