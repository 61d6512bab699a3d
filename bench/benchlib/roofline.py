"""Chip peaks, and the least time a statement needs by its shapes.

The operations and bytes, from each kind's file (``bench/kinds/``), are
those of the statement's mathematics, not of any implementation: a later
change that computes the same answer another way is measured against the
same least time.
"""

from __future__ import annotations

from . import spec

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


def least_seconds(kind: str, rows: int, k: int, device_kind: str,
                  chips: int = 1) -> float:
    """The least time ``chips`` chips could fold one statement of ``kind``
    (its file ``bench/kinds/<kind>.py`` gives the work): the larger of its
    operations over their peak FLOP/s and its bytes over their peak HBM
    bandwidth."""
    work = spec.kind(kind)
    p = peaks(device_kind)
    return max(work.flops(rows, k) / (chips * p["flops_per_s"]),
               work.bytes(rows, k) / (chips * p["hbm_bytes_per_s"]))
