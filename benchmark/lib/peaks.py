"""Published peaks of the chips this benchmark may run on.

One table, keyed by ``device_kind`` as JAX reports it.  Every utilization
and roofline share in the benchmark divides by a number from here, so a
kind that is not in the table is an error and never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s.  JAX names the chip
# "TPU v5 lite"; "TPU v5e" is kept for a runtime that renames it.
_V5E = {
    "flops_per_s": 197e12,      # bf16 matrix units; the chip has no higher
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": 'Google Cloud documentation, "TPU v5e"',
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"device kind {device_kind!r} has no row in benchmark/lib/"
            f"peaks.py; add its published peaks with their source before "
            f"measuring on it") from None
