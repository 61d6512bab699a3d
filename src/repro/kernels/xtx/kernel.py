"""Pallas TPU kernel: blocked accumulation of X^T X and X^T y.

The paper's own perf story (§4.4) is three generations of exactly this
loop: v0.1 nested-loop outer products, v0.2 untuned BLAS doing the wrong
rank-1 form (y^T y 3-4x slower than x x^T), v0.3 Eigen rank-1 symmetric
updates.  On a TPU the correct form is the **rank-TILE update**: stream
row tiles of X through VMEM and issue (K, TILE_N) @ (TILE_N, K) MXU
contractions into a persistent (K, K) VMEM accumulator.  The tiles
arrive transposed, rows on lanes, so neither X (K < 128) nor y (one
value per row) pays lane padding.

Grid: 1-D over row tiles.  Both outputs map every grid step to the same
(0, 0) block, so they live in VMEM across the whole grid (sequential TPU
grid semantics) — initialized at step 0, accumulated thereafter.

VMEM budget per step: K*TILE_N (x tile) + K*K (accumulator) + 8*TILE_N
(y row, sublane-padded) + 8*K (xty) floats.  For K ≤ 512, TILE_N = 1024:
4*(512k + 256k) ≈ 3 MB — comfortably inside the 16 MB default scoped
VMEM, leaving room for double buffering of the streamed tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_NT = (((1,), (1,)), ((), ()))   # contract the lane (row) axis of both


def _xtx_kernel(xt_ref, y_ref, xtx_ref, xty_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        xtx_ref[...] = jnp.zeros_like(xtx_ref)
        xty_ref[...] = jnp.zeros_like(xty_ref)

    xt = xt_ref[...].astype(jnp.float32)   # (K, TILE_N): rows on lanes
    y = y_ref[...].astype(jnp.float32)     # (1, TILE_N)
    # rank-TILE symmetric update on the MXU; accumulate in f32 at full
    # f32 precision (a bf16 pass would round every product)
    xtx_ref[...] += jax.lax.dot_general(
        xt, xt, _NT, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    xty_ref[...] += jax.lax.dot_general(
        y, xt, _NT, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def xtx_xty_padded(xt: jax.Array, y: jax.Array, *, tile_n: int,
                   interpret: bool):
    """xt: (K, N) — x transposed — and y: (1, N), with N % tile_n == 0
    and tile_n % 128 == 0 (pre-padded by ops.py).

    Returns (xtx (K, K) f32, xty (1, K) f32).
    """
    k, n = xt.shape
    assert n % tile_n == 0, (n, tile_n)
    grid = (n // tile_n,)
    return pl.pallas_call(
        _xtx_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((k, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(xt, y)
