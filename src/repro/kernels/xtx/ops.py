"""Public wrapper for the xtx kernel: layout + dispatch policy.

The kernel takes X transposed (rows on lanes) and y as one lane-dense
row; N is padded to the tile with zero rows (zeros contribute nothing to
either accumulation — the same masking trick the UDA transition uses).
On non-TPU backends the kernel runs in interpret mode (correctness
path); TPU gets the compiled kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import xtx_xty_padded


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


@functools.partial(jax.jit, static_argnames=("tile_n",))
def xtx_xty(x: jax.Array, y: jax.Array, *, tile_n: int = 1024):
    """(N, K), (N,) -> (X^T X (K,K) f32, X^T y (K,) f32) for any N, K."""
    n = x.shape[0]
    tile = min(_round_up(tile_n, 128), _round_up(max(n, 1), 128))
    np_ = _round_up(n, tile)
    xt = jnp.pad(x.T, ((0, 0), (0, np_ - n)))
    y = jnp.pad(y, (0, np_ - n))[None, :]
    interpret = jax.default_backend() != "tpu"
    xtx, xty = xtx_xty_padded(xt, y, tile_n=tile, interpret=interpret)
    return xtx, xty[0]
