"""The benchmark's tables, made on the device from the seed.

One jitted call builds every column of a configuration (``configs/*.json``)
from ``--seed``: ``x`` row chunk by row chunk into one buffer (a one-shot
``jax.random.normal`` of the whole column needs twice its size), ``y = x.b
+ noise`` and a GROUP BY key ``g`` uniform over ``groups`` ids.  The same
seed gives the same columns; every seed gives the same shapes.  A table
spread over several chips is made row-sharded, each chip drawing only the
rows it holds (:func:`_make_sharded`): ``x`` and ``g`` hold the same
values as on one chip.  ``y`` is summed over ``k`` in the order XLA picks
for the rows one program holds, so it may differ from the one-chip build
by rounding: on a TPU v5e at k=320 a 2.5M-row shard sums in four windows
of 80 where 10M rows on one chip sum in one (at k=80 both sum in one).
The reference reads the columns the program was given, so ``correct`` is
judged alike either way.  (For a table of a single generation chunk,
smaller than any configuration's, XLA may also fuse the one-chip draw into
``y``'s matmul.)

A one-chip cell keeps :func:`_make`.  Over a one-device mesh
:func:`_make_sharded` gives its bits at fig4_k80 on a TPU v5e, in a warm
build of 64 ms against 49 ms, but its columns carry that mesh's sharding
into the program under test, whose timed path has not been measured so.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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


@partial(jax.jit, static_argnames=("mesh", "rows", "k", "noise", "groups",
                                   "columns"))
def _make_sharded(key, *, mesh, rows, k, noise, groups, columns):
    """:func:`_make`'s columns, row-sharded over ``mesh``'s ``"data"``
    axis, each chip drawing only the rows it holds: chunk ``i`` of ``x``
    is still ``fold_in(kx, i)``, drawn whole by every chip it overlaps
    and cut to that chip's rows, chunks in order, the clipped last one
    last.  ``y``'s noise and ``g`` are drawn with the row sharding
    (threefry is partitionable: each chip draws its own rows, the values
    those of the unsharded draw)."""
    kb, kx, ke, kg = jax.random.split(key, 4)
    b = jax.random.normal(kb, (k,), jnp.float32)
    chunk = min(GEN_CHUNK, rows)
    steps = -(-rows // chunk)
    share = rows // mesh.shape["data"]
    w = min(chunk, share)                     # columns written per chunk

    def local(kx):
        lo = jax.lax.axis_index("data") * share   # first global row held

        def fill(i, xt):
            start = jnp.minimum(i * chunk, rows - chunk)
            part = jax.random.normal(jax.random.fold_in(kx, i), (k, chunk),
                                     jnp.float32)
            # the w held columns from ``at`` take the chunk's rows where it
            # covers them and keep their own elsewhere
            at = jnp.clip(start - lo, 0, share - w)
            src = jnp.arange(w) + (at + lo - start)
            win = jax.lax.dynamic_slice_in_dim(xt, at, w, 1)
            new = jnp.roll(part, start - lo - at, axis=1)[:, :w]
            upd = jnp.where((src >= 0) & (src < chunk), new, win)
            return jax.lax.dynamic_update_slice_in_dim(xt, upd, at, 1)

        first = lo // chunk
        last = jnp.where(rows - chunk < lo + share, steps - 1,
                         (lo + share - 1) // chunk)
        xt = jax.lax.pcast(jnp.zeros((k, share), jnp.float32), "data",
                           to="varying")
        return jax.lax.fori_loop(first, last + 1, fill, xt)

    xt = jax.shard_map(local, mesh=mesh, in_specs=P(),
                       out_specs=P(None, "data"))(kx)
    rows_on = NamedSharding(mesh, P("data"))
    y = jnp.matmul(b, xt, precision=jax.lax.Precision.HIGHEST)
    e = jax.lax.with_sharding_constraint(
        jax.random.normal(ke, (rows,), jnp.float32), rows_on)
    out = {"x": xt.T, "y": y + noise * e}
    if "g" in columns:
        out["g"] = jax.lax.with_sharding_constraint(
            jax.random.randint(kg, (rows,), 0, groups, jnp.int32), rows_on)
    return out


def make_columns(cfg: dict, seed: int, rows: int | None = None,
                 mesh: Mesh | None = None) -> dict:
    """Every column of ``cfg``'s table for ``seed``: on the default
    device, or row-sharded over ``mesh``'s ``"data"`` axis and made there
    chip by chip, with the same values (``y`` up to the order of its sum).
    ``rows`` overrides the configuration's row count."""
    if mesh is None:
        return _make(seed_key(seed), **table_args(cfg, rows))
    return _make_sharded(seed_key(seed), mesh=mesh,
                         **table_args(cfg, rows))


def table_args(cfg: dict, rows: int | None = None) -> dict:
    return dict(rows=int(rows or cfg["rows"]), k=int(cfg["k"]),
                noise=float(cfg["noise"]),
                groups=int(cfg.get("groups", 1)),
                columns=tuple(cfg["columns"]))
