# The v5e row is copied from src/repro/launch/roofline.py (DEVICE_PEAKS).
"""Published peak rates per chip, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" is the TPU v5e: 197 TFLOP/s in bf16, 393 TOP/s in int8 and
16 GB of HBM at 819 GB/s per chip (Google Cloud documentation, "TPU v5e").
A kind that is not here is an error, never a default: a share of another
chip's peak means nothing.
"""
from __future__ import annotations

from typing import Dict

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    """The peak row of ``device_kind``; ``KeyError`` for an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return dict(PEAKS[device_kind])
