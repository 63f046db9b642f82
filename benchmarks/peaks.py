"""Peak rates by ``device_kind``. A device that is not here is an error.

Published: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, per chip. The
calibration readings beside them are this repository's own, taken once
on the chip by ``calibrate.py`` (PERF.md section 2 gives the run); a
roofline share is taken against the PUBLISHED peak, so that it can
only be read too low, never above 100%.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": {"bf16": 197e12, "int8": 393e12},
        "bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} (add one with its source)"
        )
    return PEAKS[device_kind]
