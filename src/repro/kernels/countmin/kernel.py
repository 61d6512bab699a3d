"""Pallas TPU kernel: count-min sketch block update.

Per grid step: hash a VMEM-resident tile of items (one lane-dense row)
with ``depth`` multiply-shift/fmix32 functions, expand each hash row to
a one-hot (WIDTH, TILE) mask and reduce it over the tile with one
masked MXU contraction, accumulating into the persistent (DEPTH, WIDTH)
sketch block.

The scatter-free formulation matters: TPUs have no efficient in-VMEM
scatter-add; the iota-compare + reduction is the idiomatic replacement.
The one-hot is the kernel's largest VMEM intermediate, so the tile
shrinks as the width grows (``ops.max_tile``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
           0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


_NT = (((1,), (1,)), ((), ()))   # contract the lane (row) axis of both


def _kernel(items_ref, mask_ref, sketch_ref, *, depth: int, width: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sketch_ref[...] = jnp.zeros_like(sketch_ref)

    items = items_ref[...].astype(jnp.uint32)              # (1, T)
    mask = mask_ref[...].astype(jnp.float32)               # (1, T)
    t = items.shape[1]
    bucket = jax.lax.broadcasted_iota(jnp.int32, (width, t), 0)
    for d in range(depth):                                 # static unroll
        mult = jnp.uint32(_PRIMES[d])
        h = _fmix32(items * mult + mult)
        idx = (h % jnp.uint32(width)).astype(jnp.int32)    # (1, T)
        onehot = (bucket == idx).astype(jnp.float32)       # (WIDTH, T)
        # per-bucket counts on the MXU: exact, 0/1 operands and integer
        # sums far below 2**24 in the f32 accumulator
        counts = jax.lax.dot_general(mask, onehot, _NT,
                                     preferred_element_type=jnp.float32)
        sketch_ref[pl.ds(d, 1), :] += counts.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("depth", "width", "tile_n", "interpret"))
def countmin_padded(items, mask, *, depth: int, width: int,
                    tile_n: int, interpret: bool):
    """items (1,N) i32, mask (1,N) i32, N % tile_n == 0 -> (depth,width)."""
    n = items.shape[1]
    grid = (n // tile_n,)
    return pl.pallas_call(
        functools.partial(_kernel, depth=depth, width=width),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((depth, width), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((depth, width), jnp.int32),
        interpret=interpret,
    )(items, mask)
