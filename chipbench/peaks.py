"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

"TPU v5 lite" is the TPU v5e (Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16 and 16 GB of HBM at 819 GB/s per chip).  The bf16
figure is the chip's highest float rate, so a share of it is never
overstated for float32 work.  A device that is not in the table is an
error: a wrong peak would mis-scale every roofline share.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r};"
                         f" add them to chipbench/peaks.py with their source"
                         ) from None
