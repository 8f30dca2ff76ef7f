"""On-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<mix>.json``) and its correctness limits
(``bench/limits/<cell>.json``); the configuration's ``engine`` names the
driver (``bench/drivers/<engine>.py``) and each per-layer metric has its
reader (``bench/metrics/<metric>.py``). So a cell, a mix or a metric is
added with a file and an entry, and nothing here changes.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the harness's own counters. Either way the run checks what the
timed path produced against the plain float32 reference and prints each
number compared beside its limit. The last line of standard output is the
result. The run exits non-zero, with no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

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


class Context:
    """What a driver needs from the harness: the clock, host annotations,
    the devices, and the points where set-up ends and the window opens."""

    def __init__(self, jax, devices, meter, trace_dir: Path):
        self.jax = jax
        self.devices = devices
        self.meter = meter
        self.trace_dir = trace_dir
        self.clock = time.perf_counter
        self.setup_s = None
        self.setup_compiles = None
        self.window_compiles = None
        self.tracing = False

    def annotate(self, name: str):
        if self.tracing:
            return self.jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def setup_done(self):
        self.setup_s = self.clock() - T_START
        self.setup_compiles = self.meter.mark()

    @contextlib.contextmanager
    def window(self, trace: bool):
        if trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(str(self.trace_dir),
                                          profiler_options=opts)
            self.tracing = True
        before = self.meter.mark()
        try:
            yield
        finally:
            after = self.meter.mark()
            self.window_compiles = (after[3] - before[3],
                                    after[1] - before[1])
            if trace:
                self.jax.profiler.stop_trace()
                self.tracing = False


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload)
    import jax
    common.use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")
    from bench.peaks import peaks_for
    out, run, ctx = execute(cell, args.seed, args.seconds, bool(args.trace),
                            jax, devices[:cell.chips],
                            peaks_for(devices[0].device_kind))
    report(cell, out, run, ctx)
    return 0


def execute(cell, seed: int, seconds: float, trace: bool, jax, devices,
            peaks, control=None):
    """Run the cell on ``devices``; returns the result object, the driver's
    record and the context. The chip check is the caller's. ``control``
    puts the reference at that precision in the program's place in the
    comparison (``bench/calibrate.py`` and the tests; never a benchmark
    run)."""
    meter = common.CompileMeter(jax)
    ctx = Context(jax, devices, meter, common.TRACE_DIR / cell.name)
    driver = common.load_driver(cell.config["engine"])
    run = driver.run(cell, seed, seconds, trace, ctx, control=control)
    run.config, run.peaks = cell.config, peaks
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed}
    if trace:
        from bench import trace as tr
        run.trace = tr.summarize(tr.find_xplane(str(ctx.trace_dir)),
                                 devices=len(devices))
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        metrics = {}
        for m in cell.per_layer:
            v = common.load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = tr.breakdown(run.trace)
    else:
        values = dict(run.e2e, setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out, run, ctx


def report(cell, out, run, ctx) -> None:
    """The run's lines: counters, metrics and, last on standard error, each
    number compared beside its limit; the result last on standard out."""
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = out["metrics"]
    secs, programs, hits, _ = ctx.setup_compiles
    traced, compiled = ctx.window_compiles
    print(f"setup_s {ctx.setup_s:.3f}, of it compiling {secs:.3f} s over "
          f"{programs} programs ({hits} loaded from {common.CACHE_DIR}); "
          f"programs traced inside the window {traced}, compiled or "
          f"loaded {compiled}", file=sys.stderr)
    print("counters " + json.dumps(
        {k: v for k, v in run.counters.items() if not isinstance(v, list)}),
        file=sys.stderr)
    print(" ".join(f"{k} {v['value']!r} {units.get(k, '')}"
                   for k, v in metrics.items()), file=sys.stderr)
    for k, (v, lim) in run.checks.items():
        print(f"check {k} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
