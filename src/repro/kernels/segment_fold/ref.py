"""Pure-jnp oracles for the segment-fold kernels.

Each oracle replays the generic grouped path EXACTLY — the same blocked
``lax.scan``, the same per-block transition arithmetic as the aggregate's
``transition`` (including the mask-multiply forms), and the same
``.at[g].add/.max`` segment merge — so for exact-state aggregates
(integer sketches, dyadic linregr) the result is bit-identical to
:func:`repro.core.aggregates.segment_fold` run without a kernel.

All three consume the group-aligned layout of
:meth:`~repro.core.table.GroupedView.aligned_blocks`: ``n2`` permuted /
padded rows forming ``nb`` equal blocks, one group per block, with
sentinel pad blocks carrying ``gid == num_groups`` (dropped by the
out-of-range scatter, exactly as in the generic path).  They return the
fold-from-zero state stack; the caller merges it with the per-group init
states under the aggregate's own combinators.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...methods.sketches import _PRIMES, _fmix32, _hash_rows, _lowest_set_bit

_HIGHEST = jax.lax.Precision.HIGHEST   # as the linregr transition


def _block_size(n2: int, nb: int) -> int:
    if nb <= 0 or n2 % nb:
        raise ValueError(f"segment_fold ref: {n2} rows do not form {nb} "
                         "equal blocks")
    return n2 // nb


def _at(arr, b, bs: int):
    """Block ``b`` by dynamic slice, as the generic path reads it (an
    ``(nb, bs, ...)`` reshape would relayout a TPU's rows-on-lanes
    ``(n, k)`` columns)."""
    return jax.lax.dynamic_slice_in_dim(arr, b * bs, bs)


def segment_linregr_ref(x, y, valid, bgids, *, num_groups: int):
    """Whole-fold OLS state stack: (N2,K) x / (N2,) y / (N2,) valid with
    ``nb`` group-aligned blocks -> the linregr state dict stacked (G,...)."""
    nb = bgids.shape[0]
    k = x.shape[1]
    f = x.dtype
    bs = _block_size(x.shape[0], nb)
    acc = {
        "xtx": jnp.zeros((num_groups, k, k), f),
        "xty": jnp.zeros((num_groups, k), f),
        "y_sum": jnp.zeros((num_groups,), f),
        "y_sq": jnp.zeros((num_groups,), f),
        "n": jnp.zeros((num_groups,), jnp.float32),
    }

    def step(acc, b):
        xq, yq, m = _at(x, b, bs), _at(y, b, bs), _at(valid, b, bs)
        g = bgids[b]
        # the aggregate's transition, verbatim (mask-multiply forms)
        xm = xq * m[:, None].astype(xq.dtype)
        ym = yq * m.astype(yq.dtype)
        bstate = {
            "xtx": jnp.matmul(xm.T, xm, precision=_HIGHEST),
            "xty": jnp.matmul(xm.T, ym, precision=_HIGHEST),
            "y_sum": jnp.sum(ym),
            "y_sq": jnp.sum(ym * ym),
            "n": jnp.sum(m.astype(jnp.float32)),
        }
        return jax.tree.map(lambda a, b: a.at[g[None]].add(b[None]),
                            acc, bstate), None

    acc, _ = jax.lax.scan(step, acc, jnp.arange(nb))
    return acc


def segment_countmin_ref(items, valid, bgids, *, depth: int, width: int,
                         num_groups: int):
    """Whole-fold Count-Min stack: (N2,) items -> (G, depth, width) i32."""
    nb = bgids.shape[0]
    bs = _block_size(items.shape[0], nb)
    items = items.astype(jnp.int32)
    acc = jnp.zeros((num_groups, depth, width), jnp.int32)

    def step(acc, b):
        it, m, g = _at(items, b, bs), _at(valid, b, bs), bgids[b]
        idx = _hash_rows(it, depth, width)                   # (depth, bs)
        upd = m.astype(jnp.int32)
        bstate = jax.vmap(lambda s, i: s.at[i].add(upd))(
            jnp.zeros((depth, width), jnp.int32), idx)
        return acc.at[g[None]].add(bstate[None]), None

    acc, _ = jax.lax.scan(step, acc, jnp.arange(nb))
    return acc


def segment_fm_ref(items, valid, bgids, *, num_hashes: int, bits: int,
                   num_groups: int):
    """Whole-fold Flajolet-Martin stack: (N2,) items -> (G, H, bits) i32
    {0,1} bitmaps, max-merged per block."""
    nb = bgids.shape[0]
    bs = _block_size(items.shape[0], nb)
    items = items.astype(jnp.uint32)
    acc = jnp.zeros((num_groups, num_hashes, bits), jnp.int32)
    mults = _PRIMES[:num_hashes][:, None]

    def step(acc, b):
        it, m, g = _at(items, b, bs), _at(valid, b, bs), bgids[b]
        h = _fmix32(it[None, :] * mults + mults)             # (H, bs)
        r = _lowest_set_bit(h, bits)
        onehots = jax.nn.one_hot(r, bits, dtype=jnp.int32)
        onehots = onehots * m.astype(jnp.int32)[None, :, None]
        bstate = jnp.max(onehots, axis=1)                    # (H, bits)
        return acc.at[g[None]].max(bstate[None]), None

    acc, _ = jax.lax.scan(step, acc, jnp.arange(nb))
    return acc
