"""OOM flight recorder (``repro.obs.flight``).

A crash dump for memory: :class:`FlightRecorder` keeps a bounded ring
buffer of recent context — phase spans, metric samples, offload
park/fetch events, serving steps — and when HBM pressure crosses a
configurable watermark fraction (or an XLA ``RESOURCE_EXHAUSTED`` error
is caught in flight), it dumps a forensic JSON bundle: who owned how
many bytes (from the attribution snapshot), the top-k live buffers with
owner paths, and the phase history leading up to the breach.

Capacity is per device, and callers pass the live bytes of their fullest
device. Resolution, in order:
  1. explicit ``capacity_bytes`` (tests, known HBM budgets);
  2. the smallest ``memory_stats()["bytes_limit"]`` over the local devices
     (real accelerators);
  3. calibration fallback — the first ``check()`` made at a boundary (right
     after a ``phase`` or ``serve_step`` note) latches its own live bytes
     as capacity, so a *forced* low watermark (< 1.0) still triggers
     deterministically on backends (CPU) that report no limit. Boundaries
     follow the trainer's memory hygiene, so garbage that earlier work in
     the process left uncollected is not latched into the budget; checks
     before the first boundary (mid-phase samples) are skipped.

The recorder is a pure observer: it never frees, never retries, never
swallows the exception — ``record_oom`` captures and the caller
re-raises. Each trigger kind fires at most once per recorder (latched)
so a breached run doesn't dump on every subsequent boundary.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

__all__ = ["FlightRecorder"]

SCHEMA = "flight-recorder/v1"


# context notes that mark a boundary: the calibration fallback latches there
BOUNDARY_EVENTS = ("phase", "serve_step")


def _device_bytes_limit() -> Optional[int]:
    """Smallest per-device ``bytes_limit`` over the local devices, or None
    where the backend reports none (CPU)."""
    import jax
    limits = [int((d.memory_stats() or {}).get("bytes_limit", 0))
              for d in jax.local_devices()]
    return min(limits) if limits and all(limits) else None


class FlightRecorder:
    """Watermark-triggered forensic memory dump.

    Parameters
    ----------
    watermark : fraction of capacity at which ``check()`` trips.
    capacity_bytes : per-device HBM budget; None -> device bytes_limit,
        else the calibration fallback described in the module docstring.
    ring : max retained context events (spans/samples/offload events).
    top_k : live buffers listed in the dump.
    path : when set, each dump is also written to ``path`` (a single
        trigger) or ``path`` with an index suffix for later triggers.
    """

    def __init__(self, watermark: float = 0.92,
                 capacity_bytes: Optional[int] = None, ring: int = 256,
                 top_k: int = 10, path: Optional[str] = None):
        self.watermark = float(watermark)
        self.capacity_bytes = capacity_bytes if capacity_bytes \
            else _device_bytes_limit()
        self._calibrated = self.capacity_bytes is not None
        self.top_k = top_k
        self.path = path
        self.ring: deque = deque(maxlen=ring)
        self.phase_history: deque = deque(maxlen=64)
        self.dumps: List[dict] = []
        self.triggered: Dict[str, bool] = {}

    # ------------------------------------------------------------- context
    def note(self, event: str, **payload) -> None:
        """Push one context event into the ring (cheap; no walk)."""
        rec = {"event": event, "t": time.time()}
        rec.update(payload)
        self.ring.append(rec)
        if event == "phase":
            self.phase_history.append(
                {k: payload.get(k) for k in
                 ("phase", "live_bytes", "host_bytes") if k in payload})

    # ------------------------------------------------------------ triggers
    def check(self, live_bytes: int,
              snapshot_fn: Optional[Callable[[], Any]] = None,
              phase: Optional[str] = None, source: str = "") -> Optional[dict]:
        """Trip on ``live_bytes >= watermark * capacity``. The snapshot is
        taken lazily (only on a trigger) so the steady-state cost of a
        check is two comparisons."""
        if not self._calibrated:
            # CPU fallback: latch the first boundary observation as the
            # budget so a forced watermark < 1.0 still has something to
            # breach. The calibration sample itself cannot breach (it IS
            # the budget); the next check that reaches watermark * this
            # value trips.
            if self.ring and self.ring[-1]["event"] in BOUNDARY_EVENTS:
                self.capacity_bytes = max(int(live_bytes), 1)
                self._calibrated = True
            return None
        if self.triggered.get("watermark"):
            return None
        if live_bytes < self.watermark * self.capacity_bytes:
            return None
        self.triggered["watermark"] = True
        return self._dump("watermark", live_bytes=int(live_bytes),
                          snapshot_fn=snapshot_fn, phase=phase,
                          source=source)

    @staticmethod
    def is_oom(exc: BaseException) -> bool:
        return "RESOURCE_EXHAUSTED" in repr(exc)

    def record_oom(self, exc: BaseException,
                   snapshot_fn: Optional[Callable[[], Any]] = None,
                   live_bytes: int = 0, phase: Optional[str] = None,
                   source: str = "") -> Optional[dict]:
        """Capture a dump for a caught ``RESOURCE_EXHAUSTED``. The caller
        re-raises; the recorder only observes."""
        if self.triggered.get("resource_exhausted"):
            return None
        self.triggered["resource_exhausted"] = True
        return self._dump("resource_exhausted", live_bytes=int(live_bytes),
                          snapshot_fn=snapshot_fn, phase=phase,
                          source=source, error=repr(exc)[:2000])

    # ---------------------------------------------------------------- dump
    def _dump(self, trigger: str, *, live_bytes: int, snapshot_fn,
              phase: Optional[str], source: str,
              error: Optional[str] = None) -> dict:
        snap = None
        if snapshot_fn is not None:
            try:
                snap = snapshot_fn()
            except Exception:
                snap = None
        owners = dict(getattr(snap, "owners", {}) or {})
        owners = {k: v for k, v in owners.items() if v}
        bundle = {
            "schema": SCHEMA,
            "trigger": trigger,
            "t_wall": time.time(),
            "source": source,
            "phase": phase,
            "live_bytes": live_bytes,
            "capacity_bytes": self.capacity_bytes,
            "watermark": self.watermark,
            "owners": owners,
            "owners_ranked": [k for k, _ in sorted(owners.items(),
                                                   key=lambda kv: -kv[1])],
            "unattributed": int(getattr(snap, "unattributed", 0)),
            "host_owners": dict(getattr(snap, "host_owners", {}) or {}),
            "top_buffers": list(getattr(snap, "top_buffers",
                                        []) or [])[:self.top_k],
            "phase_history": list(self.phase_history),
            "ring": list(self.ring),
        }
        if error is not None:
            bundle["error"] = error
        self.dumps.append(bundle)
        if self.path:
            path = self.path if len(self.dumps) == 1 else \
                f"{self.path}.{len(self.dumps) - 1}"
            try:
                d = os.path.dirname(path)
                if d:
                    os.makedirs(d, exist_ok=True)
                with open(path, "w") as fh:
                    json.dump(bundle, fh, indent=1, default=str)
            except OSError:
                pass
        return bundle
