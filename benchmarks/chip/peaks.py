"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error: every roofline and utilization needs its own chip's peaks.

These are the benchmark's numbers; the program's own constants (for
example in ``launch/mesh.py``) play no part in any metric.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture): per chip 197 TFLOP/s bf16, 393 TOP/s "
                  "int8, 16 GiB HBM2 at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
