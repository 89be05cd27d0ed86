"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s)."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
