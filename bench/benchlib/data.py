"""The benchmark's tables, made on the device from the seed.

One jitted call builds every column of a configuration (``configs/*.json``)
from ``--seed``: ``x`` row chunk by row chunk into one buffer (a one-shot
``jax.random.normal`` of the whole column needs twice its size), ``y = x.b
+ noise`` and a GROUP BY key ``g`` uniform over ``groups`` ids.  The same
seed gives the same columns; every seed gives the same shapes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

GEN_CHUNK = 1 << 18          # rows of x drawn per step


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64 bits and more included."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("rows", "k", "noise", "groups",
                                   "columns"))
def _make(key, *, rows, k, noise, groups, columns):
    kb, kx, ke, kg = jax.random.split(key, 4)
    b = jax.random.normal(kb, (k,), jnp.float32)
    chunk = min(GEN_CHUNK, rows)
    steps = -(-rows // chunk)

    def fill(i, xt):
        # the last chunk is clipped to end at ``rows`` (it may rewrite
        # rows of the one before it: still a function of the seed alone)
        start = jnp.minimum(i * chunk, rows - chunk)
        part = jax.random.normal(jax.random.fold_in(kx, i), (k, chunk),
                                 jnp.float32)
        return jax.lax.dynamic_update_slice_in_dim(xt, part, start, 1)

    # built as (k, rows): the TPU keeps an (n, k) f32 column rows-on-lanes,
    # which is this buffer's own layout, so the transpose below is free
    # (an (n, k) loop carry would be relaid out through 128-lane tiles)
    xt = jax.lax.fori_loop(0, steps, fill,
                           jnp.zeros((k, rows), jnp.float32))
    x = xt.T
    y = jnp.matmul(b, xt, precision=jax.lax.Precision.HIGHEST)
    y = y + noise * jax.random.normal(ke, (rows,), jnp.float32)
    out = {"x": x, "y": y}
    if "g" in columns:
        out["g"] = jax.random.randint(kg, (rows,), 0, groups, jnp.int32)
    return out


def make_columns(cfg: dict, seed: int, rows: int | None = None) -> dict:
    """Every column of ``cfg``'s table for ``seed``, on the default
    device.  ``rows`` overrides the configuration's row count."""
    return _make(seed_key(seed), **table_args(cfg, rows))


def table_args(cfg: dict, rows: int | None = None) -> dict:
    return dict(rows=int(rows or cfg["rows"]), k=int(cfg["k"]),
                noise=float(cfg["noise"]),
                groups=int(cfg.get("groups", 1)),
                columns=tuple(cfg["columns"]))
