"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device kind that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s,
1,600 Gbit/s inter-chip interconnect.
"""

from __future__ import annotations

V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def for_device(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"add them to bench/peaks.py with their source")
