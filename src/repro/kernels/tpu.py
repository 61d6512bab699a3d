"""TPU facts the Pallas kernels size their blocks and support gates by.

Mosaic lays every 2-D block out in (8, 128) f32 tiles: a block's second
to last dim pads to a multiple of 8 sublanes, its last dim to a multiple
of 128 lanes.  A row-per-sublane column such as ``(N, 1)`` therefore
costs 128x its bytes, in VMEM and in HBM alike, so the kernels take
per-row vectors as lane-dense ``(1, N)`` rows and tables transposed,
rows on lanes.
"""

from __future__ import annotations

# A v5e core has 128 MiB of VMEM; Mosaic's default scoped limit is 16 MiB.
# Kernels whose resident accumulators plus double-buffered blocks need
# more declare this limit, and their support gates admit only shapes
# whose footprint (``padded_bytes`` below) fits it.
VMEM_LIMIT = 64 * 1024 * 1024


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def padded_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a ``(rows, cols)`` block after (8, 128) tiling."""
    return _round_up(max(rows, 1), 8) * _round_up(max(cols, 1), 128) \
        * itemsize
