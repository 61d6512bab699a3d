"""Split-K (sequence-sharded) decode attention — FlashDecoding on pjit.

At decode, KV caches dwarf everything (32k × 128 batch ≈ GBs/layer) and
kv-head counts (1–8) are below the 16-way tensor axis, so head-sharding
cannot scale.  Instead the cache is sharded along the **sequence** axis
over "model"; each shard computes a partial attention (max, sumexp,
weighted V) over its KV slice and the shards combine with a stable
log-sum-exp reduction — two small psums instead of gathering the cache.

Works for any kv_head count including MQA (kv=1), i.e. every assigned
arch's decode shape.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def splitk_partial(q, k_shard, v_shard, valid_shard):
    """Per-shard partials.  q (B,Hk,G,Dh); k/v (B,Sl,Hk,Dh);
    valid (B,Sl).  Returns (m (B,Hk,G), l (B,Hk,G), acc (B,Hk,G,Dh))."""
    dh = q.shape[-1]
    logits = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                        k_shard.astype(jnp.float32)) / (dh ** 0.5)
    logits = jnp.where(valid_shard[:, None, None, :], logits, -1e30)
    m = jnp.max(logits, -1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, -1)
    acc = jnp.einsum("bhgk,bkhd->bhgd", p, v_shard.astype(jnp.float32))
    return m, l, acc


def splitk_combine(m, l, acc, axis: str):
    """LSE-stable combine across the sequence-shard axis."""
    m_all = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_all)
    l_all = jax.lax.psum(l * corr, axis)
    acc_all = jax.lax.psum(acc * corr[..., None], axis)
    return acc_all / jnp.maximum(l_all, 1e-30)[..., None]


def make_splitk_decode_attention(mesh: Mesh, *, seq_axis: str = "model",
                                 batch_axes=("pod", "data")):
    """Returns attn(q (B,1,H,Dh), cache_k/v (B,S,Hk,Dh), pos (B,)) with the
    cache sharded P(batch_axes, seq_axis, None, None)."""

    def inner(q, ck, cv, pos):
        # local shard of the sequence
        sl = ck.shape[1]
        shard_idx = jax.lax.axis_index(seq_axis)
        start = shard_idx * sl
        kpos = start + jnp.arange(sl)[None, :]
        valid = kpos <= pos[:, None]
        b, one, h, dh = q.shape
        hk = ck.shape[2]
        qg = q.reshape(b, hk, h // hk, dh)
        m, l, acc = splitk_partial(qg, ck, cv, valid)
        out = splitk_combine(m, l, acc, seq_axis)
        return out.reshape(b, 1, h, dh).astype(q.dtype)

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(batch_axes, None, None, None),
                  P(batch_axes, seq_axis, None, None),
                  P(batch_axes, seq_axis, None, None),
                  P(batch_axes)),
        out_specs=P(batch_axes, None, None, None),
        check_vma=False,
    )
