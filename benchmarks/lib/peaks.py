"""Peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (System architecture): 197
TFLOP/s in bf16 per chip, 16 GB of HBM2e at 819 GB/s. A float32 matrix
product at full precision takes six bf16 passes of the matrix unit, but the
solves here are bound by bytes, so the bf16 figure only ever enters
``step_mfu_pct`` as the (never binding) flops term. A kind that is not in
the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}: add a row "
            "with its source to benchmarks/lib/peaks.py"
        ) from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for that much work: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    p = peaks_for(device_kind)
    return max(flops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
