"""Public wrappers + support gates for the segment-fold kernels.

The wrappers take the group-aligned layout exactly as ``segment_fold``
holds it — ``(N2, ...)`` permuted/padded columns, ``(N2,)`` validity,
``(nb,)`` block gids — hand the kernels the table transposed and the
per-row vectors as lane-dense ``(1, N2)`` rows (see
:mod:`repro.kernels.tpu`), and slice the state stacks back.  On non-TPU
backends the kernels run in interpret mode (the correctness path the
parity matrix pins); TPU gets the compiled kernels.

``*_supports`` answer "can the COMPILED TPU kernel take this call?"
from shapes/dtypes alone (they also run on ``ShapeDtypeStruct`` args —
the host-side resolution in ``run_grouped`` probes them before
tracing): blocks must be whole 128-lane tiles, the block-gid vector must
fit SMEM, and the VMEM footprint — resident ``(G, ...)`` accumulators,
double-buffered blocks and the per-block intermediates, in (8, 128)
tiles — must fit the kernels' declared VMEM limit.  The registry
consults them for auto dispatch on TPU and to reject a forced
``impl="pallas"`` loudly; off-TPU interpret mode has no layout
constraints, so they are not consulted there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..tpu import VMEM_LIMIT, padded_bytes
from .kernel import (
    segment_countmin_padded, segment_fm_padded, segment_linregr_padded,
)

# block-gid vector resident in SMEM for the whole grid
_SMEM_MAX_BLOCKS = 4096


def _layout(n2: int, nb: int) -> int:
    """Block size of the group-aligned layout; loud on a torn layout —
    every caller (any impl) must hand equal whole blocks."""
    if nb <= 0 or n2 % nb:
        raise ValueError(f"segment_fold kernels: {n2} rows do not form "
                         f"{nb} equal group-aligned blocks")
    return n2 // nb


def _block_ok(n2: int, nb: int) -> int | None:
    """Block size when the compiled kernels can stream this layout: equal
    blocks of whole 128-lane tiles, gids within SMEM; else None."""
    if nb <= 0 or n2 % nb or nb > _SMEM_MAX_BLOCKS:
        return None
    bs = n2 // nb
    return bs if bs % 128 == 0 else None


def _rows_in_vmem(bs: int) -> int:
    """Two lane-dense (1, BS) input rows, double-buffered."""
    return 2 * 2 * padded_bytes(1, bs)


# ---------------------------------------------------------------------------
# linregr / xtx-class
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_groups",))
def segment_linregr(x, y, valid, bgids, *, num_groups: int):
    """(N2,K) x, (N2,) y, (N2,) valid, (nb,) bgids -> stacked (G, ...)
    linregr state dict (fold-from-zero)."""
    bs = _layout(x.shape[0], bgids.shape[0])
    interpret = jax.default_backend() != "tpu"
    xtx, xty, mom = segment_linregr_padded(
        x.T, y[None, :], valid.astype(x.dtype)[None, :],
        bgids.astype(jnp.int32), num_groups=num_groups, block_size=bs,
        interpret=interpret)
    return {"xtx": xtx, "xty": xty,
            "y_sum": mom[:, 0], "y_sq": mom[:, 1], "n": mom[:, 2]}


def segment_linregr_vmem(k: int, num_groups: int, bs: int) -> int:
    """VMEM footprint of :func:`segment_linregr_padded`."""
    blocks = 2 * padded_bytes(k, bs) + _rows_in_vmem(bs)
    accs = 2 * (num_groups * padded_bytes(k, k) + padded_bytes(num_groups, k)
                + padded_bytes(num_groups, 128))
    temps = padded_bytes(k, bs) + 2 * padded_bytes(1, bs) \
        + 2 * padded_bytes(k, k)
    return blocks + accs + temps


def segment_linregr_supports(x, y, valid, bgids, *, num_groups: int):
    bs = _block_ok(x.shape[0], bgids.shape[0])
    if bs is None or x.dtype != jnp.float32:
        return False
    return segment_linregr_vmem(x.shape[1], num_groups, bs) <= VMEM_LIMIT


# ---------------------------------------------------------------------------
# sketch-class
# ---------------------------------------------------------------------------

def _rows(items, valid):
    return (items.astype(jnp.int32)[None, :],
            valid.astype(jnp.int32)[None, :])


@functools.partial(jax.jit, static_argnames=("depth", "width", "num_groups"))
def segment_countmin(items, valid, bgids, *, depth: int, width: int,
                     num_groups: int):
    """(N2,) items, (N2,) valid, (nb,) bgids -> (G, depth, width) i32."""
    bs = _layout(items.shape[0], bgids.shape[0])
    interpret = jax.default_backend() != "tpu"
    return segment_countmin_padded(
        *_rows(items, valid), bgids.astype(jnp.int32), depth=depth,
        width=width, num_groups=num_groups, block_size=bs,
        interpret=interpret)


def segment_countmin_vmem(depth: int, width: int, num_groups: int,
                          bs: int) -> int:
    """VMEM footprint of :func:`segment_countmin_padded`: the resident
    sketch stack plus the (width, BS) iota, compare and f32 one-hot."""
    return (_rows_in_vmem(bs)
            + 2 * num_groups * padded_bytes(depth, width)
            + 3 * padded_bytes(width, bs))


def segment_countmin_supports(items, valid, bgids, *, depth: int,
                              width: int, num_groups: int):
    bs = _block_ok(items.shape[0], bgids.shape[0])
    if bs is None or width % 128 or depth > 8:
        return False
    return segment_countmin_vmem(depth, width, num_groups, bs) <= VMEM_LIMIT


@functools.partial(jax.jit, static_argnames=("num_hashes", "bits",
                                             "num_groups"))
def segment_fm(items, valid, bgids, *, num_hashes: int, bits: int,
               num_groups: int):
    """(N2,) items, (N2,) valid, (nb,) bgids -> (G, H, bits) i32 bitmaps."""
    bs = _layout(items.shape[0], bgids.shape[0])
    interpret = jax.default_backend() != "tpu"
    return segment_fm_padded(
        *_rows(items, valid), bgids.astype(jnp.int32),
        num_hashes=num_hashes, bits=bits, num_groups=num_groups,
        block_size=bs, interpret=interpret)


def segment_fm_vmem(num_hashes: int, bits: int, num_groups: int,
                    bs: int) -> int:
    """VMEM footprint of :func:`segment_fm_padded`: the resident bitmap
    stack plus the (bits, BS) position iota, match, one-hot and its f32
    copy."""
    return (_rows_in_vmem(bs)
            + 2 * num_groups * padded_bytes(num_hashes, bits)
            + 4 * padded_bytes(bits, bs))


def segment_fm_supports(items, valid, bgids, *, num_hashes: int, bits: int,
                        num_groups: int):
    bs = _block_ok(items.shape[0], bgids.shape[0])
    if bs is None:
        return False
    # the (G, H, bits) stack is stored at dynamic group offsets; compiled
    # lowering wants the lane dim at the 128 boundary (default bits=32
    # stays on the jnp ref on TPU — interpret mode takes any bits)
    if bits % 128 or num_hashes > 8:
        return False
    return segment_fm_vmem(num_hashes, bits, num_groups, bs) <= VMEM_LIMIT
