"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 matrix unit
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,     # chip-to-chip interconnect
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
