"""Harness pieces shared by the drivers: the cell as ``BENCHMARK.json`` and
the files it names describe it, the compile meter, device readings, and
the record a run hands to the per-layer metric readers."""
from __future__ import annotations

import importlib.util
import json
import statistics
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    mix: dict                # bench/traffic/<traffic>.json
    limits: dict             # bench/limits/<workload>.json
    end_to_end: List[dict]   # BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything the harness knows about one workload, found by name."""
    bm = _read_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    e2e = [m for m in bm["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_read_json(root / cfg_entry["file"]),
                mix=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_read_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str):
    """The reader of one per-layer metric, ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(engine: str):
    path = BENCH / "drivers" / f"{engine}.py"
    spec = importlib.util.spec_from_file_location(f"bench_driver_{engine}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def use_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for every
    program however small or quick to compile, so that a second run of a
    cell loads everything it ran before."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileMeter:
    """Compile time and program count from JAX's own monitoring events:
    tracing, lowering and backend compilation (a persistent-cache hit is
    counted as a load, in the backend time)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration
        if event == self._DURATIONS[0]:
            self.traces += 1
        if event == self._DURATIONS[-1]:
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> Tuple[float, int, int, int]:
        return self.seconds, self.programs, self.cache_hits, self.traces


def peak_bytes(devices) -> int:
    """The peak the fullest device held: its allocator's peak of buffers in
    use plus its peak reserved for programs' temporaries, which the TPU
    runtime keeps apart and ``peak_bytes_in_use`` leaves out (0 where the
    backend keeps no such counts, as the CPU's does not)."""
    def held(d):
        s = d.memory_stats() or {}
        return int(s.get("peak_bytes_in_use", 0)) \
            + int(s.get("peak_bytes_reserved", 0))
    return max(held(d) for d in devices)


def p95(values) -> float:
    """The 95th percentile (inclusive method), as ``statistics`` gives it."""
    vals = list(values)
    if len(vals) == 1:
        return float(vals[0])
    return float(statistics.quantiles(vals, n=20, method="inclusive")[18])


@dataclass
class Run:
    """What a driver hands back: end-to-end values, the correctness checks
    (name -> (value, limit)), and what the per-layer readers read."""
    e2e: Dict[str, float]
    checks: Dict[str, Tuple[float, float]]
    attempted: int
    failed: int
    peak_bytes: int
    counters: Dict[str, Any] = field(default_factory=dict)
    trace: Optional[Any] = None          # bench.trace.Summary
    config: Optional[dict] = None
    peaks: Optional[dict] = None

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.checks.values())
