"""Pallas TPU kernel: causal GQA flash attention (forward).

Online-softmax tiling: grid (batch·q_heads, n_q_tiles, n_kv_tiles); the
innermost axis streams KV tiles through VMEM while (m, l, acc) running
statistics persist in VMEM scratch.  Causal tiles strictly above the
diagonal are skipped with ``pl.when`` (their DMA still happens — the block
index map is static — but the MXU work is elided; on TPU the bound is the
matmul, not the copy).

GQA: the q-head → kv-head mapping happens in the K/V BlockSpec index maps
(``bh // group``), so no KV replication ever materializes.

VMEM per step (f32): TILE_Q·D (q) + 2·TILE_K·D (k,v) + TILE_Q·TILE_K (s)
+ TILE_Q·(D+2) scratch.  TILE_Q=TILE_K=256, D=128: ≈ 0.8 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, tile_q: int, tile_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (qi * tile_q + tile_q - 1 >= ki * tile_k) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0]                               # (TQ, D)
        k = k_ref[0]                               # (TK, D)
        v = v_ref[0]                               # (TK, D)
        s = jax.lax.dot_general(                   # (TQ, TK)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * tile_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            cols = ki * tile_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[...]                        # (TQ, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (TQ, TK)
        l_new = alpha * l_prev + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "tile_q", "tile_k", "interpret"))
def flash_attention_padded(q, k, v, *, scale: float, causal: bool = True,
                           tile_q: int = 256, tile_k: int = 256,
                           interpret: bool):
    """q (BHq, S, D), k/v (BHk, S, D); S % tile == 0, BHq % BHk == 0.

    Returns (BHq, S, D) in q.dtype.
    """
    bhq, s, d = q.shape
    bhk = k.shape[0]
    group = bhq // bhk
    grid = (bhq, s // tile_q, s // tile_k)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          tile_q=tile_q, tile_k=tile_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
