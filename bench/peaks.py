"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  No float32 vector peak is published for the v5e, so the
roofline of the float32 stencils is a bandwidth bound only.

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9, ici_bits_per_s=1600e9),
}


def peaks(device_kind: str) -> dict:
    """The row of ``device_kind``; raises ``KeyError`` for an unlisted kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add its row to bench/peaks.py with its source"
                       ) from None
