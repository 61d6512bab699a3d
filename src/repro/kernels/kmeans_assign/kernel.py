"""Pallas TPU kernel: fused k-means assignment + partial reduction.

One streamed pass per Lloyd round (the pass standard SQL cannot express —
paper §4.3 fn.1): for each row tile in VMEM compute squared distances to
all centroids via the matmul identity (MXU), take the argmin (VPU), and
accumulate per-centroid coordinate sums + counts into persistent VMEM
accumulators via a one-hot matmul (MXU again).

Grid: 1-D over row tiles.  centroids (K, D) are re-used by every step
(constant index_map → stays resident in VMEM).  sums/counts map to block
(0, 0) every step → VMEM-persistent accumulators.

VMEM per step (f32): TILE_N*D (x) + K*D (centroids) + TILE_N*K (dists +
one-hot) + K*D (sums).  TILE_N=512, K≤1024, D≤256 → ≈ 3.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, m_ref, assign_ref, mind_ref, sums_ref, counts_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...]                                  # (T, D)
    c = c_ref[...]                                  # (K, D)
    m = m_ref[...]                                  # (T, 1)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)     # (T, 1)
    cc = jnp.sum(c * c, axis=-1)                    # (K,)
    xc = jax.lax.dot_general(                       # (T, K) on the MXU
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    d2 = xx - 2.0 * xc + cc[None, :]
    assign = jnp.argmin(d2, axis=-1)                # (T,)
    mind = jnp.min(d2, axis=-1)
    k = c.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
              == assign[:, None]).astype(jnp.float32) * m
    assign_ref[...] = assign[:, None].astype(jnp.int32)
    mind_ref[...] = jnp.maximum(mind, 0.0)[:, None] * m
    sums_ref[...] += jax.lax.dot_general(           # (K, D) one-hot matmul
        onehot, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    counts_ref[...] += jnp.sum(onehot, axis=0)[:, None]


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def assign_reduce_padded(x, c, m, *, tile_n: int, interpret: bool):
    """x (N, D), c (K, D), m (N, 1); N % tile_n == 0.

    Returns assign (N,1) i32, mind (N,1) f32, sums (K,D) f32, counts (K,1)
    f32."""
    n, d = x.shape
    k = c.shape[0]
    grid = (n // tile_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, c, m)
