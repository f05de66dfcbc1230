"""Published peaks of the chips the benchmark runs on, by the
``device_kind`` JAX reports.  No metric reads them yet: they are here
for the roofline shares the ``tracing`` issue adds (PERF.md, Open
questions).  A chip that is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"peaks.py: no published peaks for device_kind "
            f"{device_kind!r} (have: {sorted(PEAKS)}); add a row with its "
            "source before measuring on it") from None
