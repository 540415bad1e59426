"""Production meshes (TPU v5e).

Single pod: (16, 16) = ("data", "model") — 256 chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips, the "pod"
axis crossing the inter-pod DCN/ICI boundary.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import numpy as np

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run "
            "under launch/dryrun.py (it forces 512 host devices).")
    return jax.make_mesh(shape, axes, devices=devices[:n])


#: Per-chip peaks by ``device_kind``, for rooflines that MODEL a device
#: (launch/dryrun.py compiles on placeholder CPU devices and states which
#: chip it models). Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip kind; a kind not in the table is an error, never a
    default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})") from None
