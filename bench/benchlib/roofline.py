"""Chip peaks, and the work a statement needs by its shapes.

The operations and bytes are those of the statement's mathematics, not of
any implementation: a later change that computes the same answer another
way is measured against the same least time.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def linregr_flops(rows: int, k: int) -> float:
    """X^T X and X^T y over ``rows`` rows of ``k`` variables."""
    return 2.0 * rows * k * k + 2.0 * rows * k


def linregr_bytes(rows: int, k: int, itemsize: int = 4) -> float:
    """x and y read once."""
    return float(rows) * (k + 1) * itemsize


WORK = {
    "linregr": (linregr_flops, linregr_bytes),
    "grouped_linregr": (linregr_flops, linregr_bytes),
}


def least_seconds(kind: str, rows: int, k: int, device_kind: str) -> float:
    """The least time the chip could fold one statement of ``kind``:
    the larger of its operations over peak FLOP/s and its bytes over
    peak HBM bandwidth."""
    flops, nbytes = WORK[kind]
    p = peaks(device_kind)
    return max(flops(rows, k) / p["flops_per_s"],
               nbytes(rows, k) / p["hbm_bytes_per_s"])
