"""Public wrapper for the countmin kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import countmin_padded

# Bound on the (width, tile_n) one-hot the kernel holds in VMEM per
# depth row (with its iota and compare alongside).
_ONEHOT_ELEMS = 1 << 20


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def max_tile(width: int) -> int:
    """Largest row tile (whole 128-lane tiles) whose one-hot fits the
    VMEM bound at ``width``; 0 when not even one tile does."""
    return _ONEHOT_ELEMS // max(width, 1) // 128 * 128


@functools.partial(jax.jit, static_argnames=("depth", "width", "tile_n"))
def countmin_block(items: jax.Array, mask: jax.Array, depth: int, width: int,
                   *, tile_n: int = 2048) -> jax.Array:
    """(N,) items + (N,) mask -> (depth, width) int32 count increments."""
    n = items.shape[0]
    tile = min(_round_up(tile_n, 128), _round_up(max(n, 1), 128))
    np_ = _round_up(n, tile)
    ip = jnp.pad(items.astype(jnp.int32), (0, np_ - n))[None, :]
    mp = jnp.pad(mask.astype(jnp.int32), (0, np_ - n))[None, :]
    interpret = jax.default_backend() != "tpu"
    return countmin_padded(ip, mp, depth=depth, width=width, tile_n=tile,
                           interpret=interpret)
