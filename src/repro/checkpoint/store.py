"""Checkpointing: pytree <-> npz with path-keyed leaves. Sharding-aware:
arrays are gathered to host on save and re-placed with the provided
shardings on restore (per-leaf NamedSharding tree optional).

``restore(..., memory_kind=...)`` targets a memory kind instead of the
device default — with an active offload plan, trees that would be parked
immediately after resume restore straight into host memory
(``kernels.compat.host_memory_kind()``) and never transit HBM; feed them
to ``OffloadExecutor.adopt_parked``. On backends without memory kinds the
leaves stay as host numpy arrays (the parking lot's fallback
representation), which ``adopt_parked`` accepts unchanged."""
from __future__ import annotations

import json
import os
import re
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for kp, leaf in flat:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in kp)
        out[key] = np.asarray(leaf)
    return out


def save(ckpt_dir: str, step: int, tree: Any) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    tmp = path + ".tmp.npz"
    flat = _flatten(tree)
    np.savez(tmp, **flat)
    os.replace(tmp, path)
    return path


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(m.group(1)) for f in os.listdir(ckpt_dir)
             if (m := re.match(r"step_(\d+)\.npz$", f))]
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, like: Any, shardings: Any = None,
            *, memory_kind: str = None) -> Any:
    """Load step ``step`` shaped/typed like ``like``. ``memory_kind``
    (e.g. ``compat.host_memory_kind()``) retargets placement: leaves land
    in that memory space — or stay as host numpy arrays when the backend
    has no such kind — instead of spiking HBM on the way to a parking
    lot."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}.npz")
    data = np.load(path)
    flat_like = jax.tree_util.tree_flatten_with_path(like)[0]
    treedef = jax.tree_util.tree_structure(like)
    shard_leaves = (jax.tree_util.tree_flatten(shardings)[0]
                    if shardings is not None else [None] * len(flat_like))
    kind_ok = False
    if memory_kind is not None:
        from repro.kernels import compat
        kind_ok = memory_kind in (compat.host_memory_kind(),
                                  compat.device_memory_kind())
    leaves = []
    for (kp, leaf), sh in zip(flat_like, shard_leaves):
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in kp)
        arr = data[key]
        assert arr.shape == tuple(leaf.shape), (key, arr.shape, leaf.shape)
        arr = arr.astype(leaf.dtype)
        if memory_kind is not None:
            if not kind_ok:         # no such space: stay host-resident
                leaves.append(arr)
                continue
            if sh is None:
                # no target sharding given: keep the one ``like`` has;
                # only a bare shape lands on the default device
                sh = getattr(leaf, "sharding", None) or \
                    jax.sharding.SingleDeviceSharding(jax.devices()[0])
            sh = sh.with_memory_kind(memory_kind)
        leaves.append(jax.device_put(arr, sh) if sh is not None
                      else jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)
