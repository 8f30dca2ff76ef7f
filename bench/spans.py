"""The program's own spans in the profiler trace the harness just reduced.

The paged batcher writes ``serve.*`` spans (``repro.obs.tracer``) into the
profiler's host trace, each with its counts as event stats. ``events``
reads the newest ``.xplane.pb`` under ``common.TRACE_DIR`` and returns
those that lie inside the window annotation, on its thread, in order of
their start. A program that writes no such spans gives an empty list.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench import common
from bench.trace import WINDOW, find_xplane

PREFIX = "serve."


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> Tuple[Event, ...]:
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            win = [e for e in evs if e.name == WINDOW]
            if not win:
                continue
            lo, hi = win[0].start_ns, win[0].end_ns
            # a stat set again while the span was open comes later and wins
            return tuple(Event(e.name, e.start_ns, e.end_ns, dict(e.stats))
                         for e in evs if e.name.startswith(PREFIX)
                         and lo <= e.start_ns and e.end_ns <= hi)
    return ()


def events(name: Optional[str] = None) -> List[Event]:
    """The window's ``serve.*`` events, or those named ``name``."""
    try:
        path = find_xplane(str(common.TRACE_DIR))
    except FileNotFoundError:
        return []
    return [e for e in _read(path, os.path.getmtime(path))
            if name is None or e.name == name]
