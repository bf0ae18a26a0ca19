"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A chip that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect (ICI) per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "int8_op_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_byte_s": 819e9,
        "ici_byte_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
