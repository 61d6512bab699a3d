"""Pallas TPU kernels: the grouped segment fold as one fused grid loop.

Templated on the xtx/countmin kernels, extended with the segment-merge
contract of the partitioned grouped core:

* the block-gid vector rides in SMEM via scalar prefetch
  (``PrefetchScalarGridSpec``) — the grid step reads its group id before
  touching data;
* the stacked ``(G, ...)`` per-group accumulators map every grid step to
  the same block (constant index maps), so they persist in VMEM across
  the whole sequential grid — zero-initialized at step 0, accumulated
  dynamically at ``pl.ds(g, 1)`` thereafter.  The segment-boundary merge
  is thereby fused into the grid loop: no per-block states ever
  round-trip HBM;
* sentinel pad blocks (``gid == num_groups``, produced by
  ``sharded_blocks`` so every mesh segment gets whole blocks) are
  skipped by a ``pl.when`` guard — the VMEM analogue of the generic
  path's out-of-range scatter drop.

Rows ride the lanes: the table block arrives transposed, ``(K, BS)``,
and per-row vectors (y, validity, items) as ``(1, BS)`` rows, so no
operand pays the 128x lane padding of an ``(N, 1)`` column (see
:mod:`repro.kernels.tpu`).  Per-block arithmetic mirrors each
aggregate's ``transition`` (mask-multiply, MXU rank-BS updates in f32,
iota-compare one-hot reductions instead of scatters), so for exact-state
aggregates the result is bit-identical to the jnp segment fold.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tpu import VMEM_LIMIT

_PRIMES = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
           0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09)


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


# ---------------------------------------------------------------------------
# linregr / xtx-class: OLS sufficient statistics per group.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # contract the lane (row) axis of both


def _linregr_kernel(bgids_ref, xt_ref, y_ref, m_ref,
                    xtx_ref, xty_ref, mom_ref, *, num_groups: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        xtx_ref[...] = jnp.zeros_like(xtx_ref)
        xty_ref[...] = jnp.zeros_like(xty_ref)
        mom_ref[...] = jnp.zeros_like(mom_ref)

    g = bgids_ref[i]

    @pl.when(g < num_groups)  # sentinel pad blocks carry gid == num_groups
    def _update():
        m = m_ref[...]                       # (1, BS) f32 validity
        xt = xt_ref[...] * m                 # the transition's mask-multiply
        y = y_ref[...] * m
        # rank-BS symmetric update on the MXU, accumulated in f32 at full
        # f32 precision (the state is the regression's sufficient
        # statistics; a bf16 pass would round every product)
        xtx_blk = jax.lax.dot_general(
            xt, xt, _NT, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)               # (K, K)
        xty_blk = jax.lax.dot_general(
            y, xt, _NT, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)               # (1, K)
        # scalar moments packed into one 128-lane row: y_sum | y_sq | n
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        mom_blk = (jnp.where(lane == 0, jnp.sum(y), 0.0)
                   + jnp.where(lane == 1, jnp.sum(y * y), 0.0)
                   + jnp.where(lane == 2, jnp.sum(m), 0.0))
        rows = pl.ds(g, 1)
        xtx_ref[rows, :, :] += xtx_blk[None]
        xty_ref[rows, :] += xty_blk
        mom_ref[rows, :] += mom_blk


@functools.partial(jax.jit, static_argnames=("num_groups", "block_size",
                                             "interpret"))
def segment_linregr_padded(xt, y, m, bgids, *, num_groups: int,
                           block_size: int, interpret: bool):
    """xt (K, N2) f32 (the table transposed), y/m (1, N2) f32, bgids (nb,)
    i32, N2 == nb * block_size -> (xtx (G,K,K), xty (G,K), moments
    (G,128))."""
    k, n2 = xt.shape
    nb = bgids.shape[0]
    assert n2 == nb * block_size, (n2, nb, block_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((k, block_size), lambda i, g: (0, i)),
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((num_groups, k, k), lambda i, g: (0, 0, 0)),
            pl.BlockSpec((num_groups, k), lambda i, g: (0, 0)),
            pl.BlockSpec((num_groups, 128), lambda i, g: (0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_linregr_kernel, num_groups=num_groups),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((num_groups, k, k), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, k), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(bgids, xt, y, m)


# ---------------------------------------------------------------------------
# sketch-class: Count-Min (sum-merge) and Flajolet-Martin (max-merge).
# ---------------------------------------------------------------------------

def _count_rows(mask, onehot):
    """(1, BS) validity x (W, BS) one-hot -> (1, W) per-bucket counts on
    the MXU.  Exact: 0/1 operands, integer sums far below 2**24 in the
    f32 accumulator."""
    return jax.lax.dot_general(mask, onehot.astype(jnp.float32), _NT,
                               preferred_element_type=jnp.float32)


def _countmin_kernel(bgids_ref, items_ref, mask_ref, sk_ref, *,
                     depth: int, width: int, num_groups: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sk_ref[...] = jnp.zeros_like(sk_ref)

    g = bgids_ref[i]

    @pl.when(g < num_groups)
    def _update():
        items = items_ref[...].astype(jnp.uint32)           # (1, BS)
        mask = mask_ref[...].astype(jnp.float32)
        t = items.shape[1]
        bucket = jax.lax.broadcasted_iota(jnp.int32, (width, t), 0)
        for d in range(depth):                               # static unroll
            mult = jnp.uint32(_PRIMES[d])
            h = _fmix32(items * mult + mult)
            idx = (h % jnp.uint32(width)).astype(jnp.int32)
            # scatter-free: iota-compare one-hot, reduced on the MXU
            row = _count_rows(mask, bucket == idx)           # (1, width)
            sk_ref[pl.ds(g, 1), pl.ds(d, 1), :] += \
                row.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("depth", "width", "num_groups",
                                             "block_size", "interpret"))
def segment_countmin_padded(items, mask, bgids, *, depth: int, width: int,
                            num_groups: int, block_size: int,
                            interpret: bool):
    """items/mask (1, N2) i32, bgids (nb,) i32 -> (G, depth, width) i32."""
    n2 = items.shape[1]
    nb = bgids.shape[0]
    assert n2 == nb * block_size, (n2, nb, block_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
        ],
        out_specs=pl.BlockSpec((num_groups, depth, width),
                               lambda i, g: (0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_countmin_kernel, depth=depth, width=width,
                          num_groups=num_groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, depth, width), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(bgids, items, mask)


def _fm_kernel(bgids_ref, items_ref, mask_ref, bm_ref, *,
               num_hashes: int, bits: int, num_groups: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        bm_ref[...] = jnp.zeros_like(bm_ref)

    g = bgids_ref[i]

    @pl.when(g < num_groups)
    def _update():
        items = items_ref[...].astype(jnp.uint32)           # (1, BS)
        mask = mask_ref[...].astype(jnp.float32)
        t = items.shape[1]
        pos = jax.lax.broadcasted_iota(jnp.uint32, (bits, t), 0)
        for hi in range(num_hashes):                         # static unroll
            mult = jnp.uint32(_PRIMES[hi])
            h = _fmix32(items * mult + mult)
            # lowest set bit, scatter/argmax-free: isolate it as a power
            # of two and compare against the sublane's 1 << pos
            low = h & (jnp.uint32(0) - h)
            match = low == (jnp.uint32(1) << pos)            # (bits, BS)
            # no set bit in [0, bits) (h == 0 or lowest bit past the
            # window) falls back to position bits-1, as the oracle does
            none = ~jnp.any(match, axis=0, keepdims=True)
            onehot = match | ((pos == jnp.uint32(bits - 1)) & none)
            row = (_count_rows(mask, onehot) > 0).astype(jnp.int32)
            sl = (pl.ds(g, 1), pl.ds(hi, 1), slice(None))
            bm_ref[sl] = jnp.maximum(bm_ref[sl], row[None])


@functools.partial(jax.jit, static_argnames=("num_hashes", "bits",
                                             "num_groups", "block_size",
                                             "interpret"))
def segment_fm_padded(items, mask, bgids, *, num_hashes: int, bits: int,
                      num_groups: int, block_size: int,
                      interpret: bool):
    """items/mask (1, N2) i32, bgids (nb,) i32 -> (G, H, bits) i32."""
    n2 = items.shape[1]
    nb = bgids.shape[0]
    assert n2 == nb * block_size, (n2, nb, block_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
            pl.BlockSpec((1, block_size), lambda i, g: (0, i)),
        ],
        out_specs=pl.BlockSpec((num_groups, num_hashes, bits),
                               lambda i, g: (0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_fm_kernel, num_hashes=num_hashes, bits=bits,
                          num_groups=num_groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, num_hashes, bits),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(bgids, items, mask)
