"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e": per-chip peaks'

PEAKS = {
    # JAX names the v5e chip "TPU v5 lite".
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" add them to {__name__} with their source") from None
