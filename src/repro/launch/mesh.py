"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state. Single pod: 256 v5e chips as (data=16, model=16). Multi-pod:
2 pods = 512 chips as (pod=2, data=16, model=16) — the pod axis carries
pure data parallelism over DCN.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1-device mesh for CPU smoke runs."""
    return make_mesh((1, 1), ("data", "model"))


def make_zero_mesh(ndp: int = 1, *, model: int = 1,
                   devices=None) -> jax.sharding.Mesh:
    """``(data=ndp, model=...)`` mesh over the first ``ndp * model`` local
    devices — the DP/ZeRO domain of the sharded RLHF engines. Unlike
    :func:`make_mesh` this takes an explicit device subset, so one forced
    multi-device CPU process can host the ``ndp=1`` baseline and the
    ``ndp=8`` sharded run side by side (the CI validation topology)."""
    import numpy as np
    devices = list(devices if devices is not None else jax.devices())
    n = ndp * model
    assert len(devices) >= n, (len(devices), n)
    arr = np.array(devices[:n]).reshape(ndp, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s per link (~45-50 GB/s on v5e)
ICI_LINKS = 4                 # 2D torus on v5e: 4 links/chip
DCN_BW = 25e9                 # B/s per host NIC (pod axis)
