"""The yardstick's table of chip peaks, keyed by JAX's ``device_kind``.
The operations and bytes of a model's work are its family's
(``bench/models/``).

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect. A kind that is
not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 200e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None
