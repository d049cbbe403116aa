"""Peaks of one chip, keyed by the exact ``device_kind`` jax reports.

A kind that is not in the table is an error, never a default: a
utilization scored against the wrong peak reads like a measurement.
Source of every row: Google Cloud TPU documentation, per-chip figures
("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s; the chip reports
``device_kind`` "TPU v5 lite"). The bf16 column is copied from
``bench.py``'s ``PEAK_BF16_FLOPS``.
"""

from __future__ import annotations

# device_kind: (bf16 FLOP/s, HBM bytes/s, HBM bytes)
PEAKS = {
    "TPU v4": (275e12, 1228e9, 32 * 2 ** 30),
    "TPU v5 lite": (197e12, 819e9, 16 * 2 ** 30),
    "TPU v5e": (197e12, 819e9, 16 * 2 ** 30),
    "TPU v5p": (459e12, 2765e9, 95 * 2 ** 30),
    "TPU v5": (459e12, 2765e9, 95 * 2 ** 30),
    "TPU v6 lite": (918e12, 1640e9, 32 * 2 ** 30),
    "TPU v6e": (918e12, 1640e9, 32 * 2 ** 30),
}


def _row(device_kind: str):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add a "
            "row with its source to benchmark/peaks.py") from None


def peak_flops(device_kind: str) -> float:
    """Peak dense bfloat16 FLOP/s of one chip."""
    return _row(device_kind)[0]


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    return _row(device_kind)[1]
