"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``
as JAX reports it. A chip that is not in the table is an error, never a
default.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.py knows {sorted(PEAKS)}") from None
