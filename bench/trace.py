"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, time
per program, time per op, collective time and the device's idle gaps, each
gap labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per program
execution and its ``XLA Ops`` line one per HLO op. Host annotations
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines.
The harness brackets the traced window with the annotation ``WINDOW`` and
starts and stops the profiler right around it, so every device event in
the trace belongs to the window. The two clocks agree only to about a
millisecond (a v5e's first program of a window can read as starting
before the host opened it). So device events are not cut by host times;
before idle gaps are labelled (by the innermost harness annotation around
a gap's middle), the device clock is shifted so that its first op starts
when the window's first program dispatch (``PjitFunction``) did.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|allgather|allreduce|"
                        r"reducescatter", re.I)
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(raw: str) -> str:
    """``jit_decode(12)`` -> ``decode``: the program's own name (a lambda's
    program is ``_lambda``)."""
    name = _SUFFIX.sub("", raw.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(raw: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``: the HLO
    instruction's name, which XLA derives from its opcode."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


@dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over the devices traced
    busy_s_per_device: List[float]
    modules: Dict[str, List[float]]        # program -> durations, device 0
    ops: Dict[str, float]                  # op -> total seconds, device 0
    collectives_s: float                   # device 0
    idle_gaps: List[Tuple[str, float]]     # (host activity, seconds)
    devices: int = 1
    spans: Dict[str, List[float]] = field(default_factory=dict)

    def module_time(self, names: Sequence[str]) -> float:
        return sum(sum(self.modules.get(n, ())) for n in names)

    def module_mean(self, names: Sequence[str]) -> Optional[float]:
        d = [x for n in names for x in self.modules.get(n, ())]
        return sum(d) / len(d) if d else None


def union_length(intervals: Sequence[Tuple[float, float]],
                 lo: float, hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``, and the gaps between them inside that range."""
    busy, gaps, cur = 0.0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@dataclass
class Device:
    busy_s: float
    gaps: List[Tuple[float, float]]       # ns, between busy intervals
    modules: Dict[str, List[float]]
    ops: Dict[str, float]
    collectives_s: float
    first_ns: Optional[float]


def reduce_device(lines: Dict[str, list]) -> Device:
    """One device's lines (name -> [(event, start_ns, end_ns)]) reduced:
    the union of its op intervals, the gaps between them, seconds per
    program execution and per op, and seconds in collective ops."""
    op_ev = lines.get("XLA Ops") or lines.get("XLA Modules") or []
    if not op_ev:
        return Device(0.0, [], {}, {}, 0.0, None)
    first = min(s for _, s, _ in op_ev)
    busy, gaps = union_length([(s, e) for _, s, e in op_ev], first,
                              max(e for _, _, e in op_ev))
    modules, ops, coll = defaultdict(list), defaultdict(float), 0.0
    for n, s, e in lines.get("XLA Modules", []):
        modules[module_name(n)].append((e - s) * 1e-9)
    for n, s, e in op_ev:
        name = op_name(n)
        ops[name] += (e - s) * 1e-9
        if COLLECTIVE.search(name):
            coll += (e - s) * 1e-9
    return Device(busy * 1e-9, gaps, dict(modules), dict(ops), coll, first)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def summarize(path: str, devices: int = 1, gaps: int = 10) -> Summary:
    """Reduce the trace at ``path`` over the first ``devices`` TPUs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_lines: List[List[Tuple[str, float, float]]] = []
    dev_lines: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            idx = int(m.group(1))
            if idx < devices:
                dev_lines[idx] = {line.name: list(_events(line))
                                  for line in plane.lines}
        elif plane.name.startswith("/host:"):
            host_lines.extend(list(_events(line)) for line in plane.lines)
    # the harness's own thread: the line that holds the window annotation
    main = [ln for ln in host_lines if any(n == WINDOW for n, _, _ in ln)]
    if not main:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    host_spans = main[0]
    lo, hi = next((s, e) for n, s, e in host_spans if n == WINDOW)
    if not dev_lines:
        raise ValueError(f"{path}: no TPU plane")
    per_dev = [reduce_device(dev_lines[i]) for i in sorted(dev_lines)]
    busy = [d.busy_s for d in per_dev]
    dev0 = per_dev[0]
    spans = defaultdict(list)
    inner = [(n, s, e) for n, s, e in host_spans
             if n != WINDOW and lo <= s and e <= hi]
    for n, s, e in inner:
        spans[n].append((e - s) * 1e-9)
    dispatch = min((s for n, s, _ in inner if n.startswith("PjitFunction")),
                   default=None)
    shift = dispatch - dev0.first_ns \
        if dispatch is not None and dev0.first_ns is not None else 0.0
    labelled = []
    for s, e in sorted(dev0.gaps, key=lambda g: g[0] - g[1])[:gaps]:
        mid = (s + e) / 2 + shift
        cover = [(ee - ss, n) for n, ss, ee in inner if ss <= mid <= ee]
        labelled.append((min(cover)[1] if cover else "none",
                         (e - s) * 1e-9))
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=sum(busy) / len(busy),
                   busy_s_per_device=busy, modules=dev0.modules,
                   ops=dev0.ops, collectives_s=dev0.collectives_s,
                   idle_gaps=labelled, devices=len(busy), spans=dict(spans))


def breakdown(s: Summary, top: int = 10) -> dict:
    """The trace's top device ops and longest idle gaps, for the result."""
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in s.idle_gaps[:top]]}
