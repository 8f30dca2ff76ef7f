"""The single capability probe for JAX *memory kinds* (the ``device`` /
``pinned_host`` spaces behind ``jax.device_put``-based host offload).
Everything in ``repro.offload`` and the sharding rules gates on these three
functions rather than sniffing the backend again:

  * :func:`host_memory_kind`   — the host space a device can park arrays
    in, or ``None`` when the backend exposes none besides its default;
  * :func:`device_memory_kind` — the default memory kind;
  * :func:`supports_host_offload` — convenience predicate.

Under jax 0.9 both the CPU and the TPU backend expose ``device`` (the
default), ``pinned_host`` and ``unpinned_host``, so offload parks in
``pinned_host`` on both. On a backend where :func:`host_memory_kind` is
``None``, offload callers fall back to committed host copies (``numpy``
round trips through ``jax.device_put``) — bit-identical, just without the
pinned DMA path.
"""
from __future__ import annotations

import functools
from typing import Optional


@functools.lru_cache(maxsize=None)
def _memory_probe():
    """(default_kind, frozenset(all kinds)) of device 0."""
    import jax
    dev = jax.devices()[0]
    kinds = frozenset(m.kind for m in dev.addressable_memories())
    return dev.default_memory().kind, kinds


def device_memory_kind() -> str:
    """Memory kind of the default space ("device" on CPU and TPU)."""
    return _memory_probe()[0]


def host_memory_kind() -> Optional[str]:
    """The host memory kind usable as a ``jax.device_put`` target for
    offload, or None when the backend exposes no space distinct from its
    default. Prefers "pinned_host" (DMA-able) over "unpinned_host"."""
    default, kinds = _memory_probe()
    for kind in ("pinned_host", "unpinned_host"):
        if kind in kinds and kind != default:
            return kind
    return None


def supports_host_offload() -> bool:
    """True when runtime HBM<->host swapping can use real memory-kind
    placement (vs the committed-numpy fallback)."""
    return host_memory_kind() is not None


__all__ = ["device_memory_kind", "host_memory_kind",
           "supports_host_offload"]
