"""Sequence-sharded all-to-all expert parallelism (beyond-paper §Perf).

The baseline MoE (models/moe.py) keeps tokens replicated across the
tensor axis: every model shard gathers all tokens, runs its expert slice,
and the combine is a full (tokens × d_model) **all-reduce** per layer —
the dominant collective in the dbrx/moonshot train cells.

This implementation shards tokens over the tensor axis too (sequence
sharding at the MoE boundary) and moves only routed token embeddings with
two **all-to-alls** (dispatch + return), after which the combine is a
purely local segment-sum:

  wire/layer/device ≈ 2 · (n_loc · k · cf / EP) · d · bytes   (a2a)
    vs ≈ 2 · 2 · n_grp · d · bytes                            (all-reduce)

  — an ~EP/k× reduction (dbrx: 16/4 = 4×; moonshot: 16/6 ≈ 2.7× on wire
  plus the f32→bf16 payload halving).

Layout inside shard_map over (batch_axes…, "model"):
  x_loc (B_loc, S_loc, d); per-shard routing + capacity bucketing;
  (E, C_loc, d) -> reshape (EP, E_loc, C_loc, d) -> all_to_all ->
  (E_loc, EP·C_loc, d) -> local expert SwiGLU (weights all-gathered over
  the FSDP axis, as XLA does implicitly in the pjit path) -> reverse
  all_to_all -> local combine.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.layers import ParamStore



def init_moe_a2a(store: ParamStore, cfg, name="moe"):
    """Same parameter shapes as the baseline MoE; the router is replicated
    (tiny), expert weights are (expert × fsdp)-sharded."""
    sub = store.subtree(name)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    sub.add("router", (d, e), (None, None), scale=d ** -0.5)
    sub.add("w_gate", (e, d, f), ("expert", "fsdp", None))
    sub.add("w_up", (e, d, f), ("expert", "fsdp", None))
    sub.add("w_down", (e, f, d), ("expert", None, "fsdp"))
    return sub


def _local_dispatch(xf, logits, e, k, cap):
    """Per-shard capacity bucketing (same algorithm as the baseline)."""
    n = xf.shape[0]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), 1),
                  axis=0)
    aux = e * jnp.sum(me * ce)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sp, stok = flat_e[order], flat_p[order], flat_tok[order]
    pos_in_e = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
    keep = pos_in_e < cap
    slot = jnp.where(keep, se * cap + pos_in_e, e * cap)
    tok_buf = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(
        stok.astype(jnp.int32))
    w_buf = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sp, 0.0))
    v_buf = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(
        keep.astype(jnp.float32))
    return (tok_buf[:-1].reshape(e, cap), w_buf[:-1].reshape(e, cap),
            v_buf[:-1].reshape(e, cap), aux)


def make_run_moe_a2a(mesh: Mesh, cfg, *, batch_axes=("pod", "data"),
                     expert_axis: str = "model", fsdp_axis: str = "data"):
    """Returns moe_fn(params, x) with x sharded
    P(batch_axes, expert_axis, None) — sequence-sharded at entry."""
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    ep = mesh.shape[expert_axis]
    e, k = cfg.n_experts, cfg.top_k
    assert e % ep == 0, (e, ep)
    e_loc = e // ep

    def shard_fn(router, w_gate, w_up, w_down, x):
        b_loc, s_loc, d = x.shape
        n_loc = b_loc * s_loc
        xf = x.reshape(n_loc, d)
        cap = max(8, -(-int(n_loc * k * cfg.capacity_factor / e) // 8) * 8)

        logits = (xf @ router).astype(jnp.float32)
        tok_ec, w_ec, v_ec, aux = _local_dispatch(xf, logits, e, k, cap)
        xe = (xf[tok_ec] * v_ec[..., None].astype(x.dtype))  # (E, C, d)

        # ---- dispatch all-to-all over the expert axis ----
        xe = xe.reshape(ep, e_loc, cap, d)
        recv = jax.lax.all_to_all(xe, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (EP, e_loc, C, d) — [j] = tokens from source shard j
        recv = jnp.moveaxis(recv, 0, 1).reshape(e_loc, ep * cap, d)

        # ---- local experts (weights FSDP-gathered, as pjit would) ----
        # preferred_element_type keeps operands in bf16 across the FSDP
        # gathers (otherwise XLA hoists a f32 convert before the
        # all-gather and doubles the wire bytes)
        wg = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
        # pin the gather->compute boundary: stops XLA hoisting the f32
        # convert above the all-gather (which doubles wire bytes; the CPU
        # cost model is collective-blind)
        wg, wu, wd = jax.lax.optimization_barrier((wg, wu, wd))
        acc = jnp.float32
        gate = jnp.einsum("ecd,edf->ecf", recv, wg,
                          preferred_element_type=acc)
        up = jnp.einsum("ecd,edf->ecf", recv, wu,
                        preferred_element_type=acc)
        hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        out = jnp.einsum("ecf,efd->ecd", hidden, wd,
                         preferred_element_type=acc).astype(x.dtype)

        # ---- return all-to-all ----
        out = out.reshape(e_loc, ep, cap, d)
        out = jnp.moveaxis(out, 1, 0)                       # (EP, e_loc, C, d)
        back = jax.lax.all_to_all(out, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(e, cap, d)

        # ---- local combine ----
        back = back * (w_ec * v_ec)[..., None].astype(x.dtype)
        combined = jnp.zeros((n_loc, d), back.dtype).at[
            tok_ec.reshape(-1)].add(back.reshape(e * cap, d))
        aux = jax.lax.pmean(jax.lax.pmean(aux, expert_axis),
                            batch_axes) if batch_axes else \
            jax.lax.pmean(aux, expert_axis)
        drop = 1.0 - jnp.sum(v_ec) / jnp.maximum(n_loc * k, 1)
        drop = jax.lax.pmean(jax.lax.pmean(drop, expert_axis),
                             batch_axes) if batch_axes else \
            jax.lax.pmean(drop, expert_axis)
        return (combined.reshape(b_loc, s_loc, d).astype(x.dtype),
                aux * cfg.router_aux_weight, drop)

    in_specs = (
        P(),                                    # router (replicated)
        P(expert_axis, fsdp_axis, None),        # w_gate
        P(expert_axis, fsdp_axis, None),        # w_up
        P(expert_axis, None, fsdp_axis),        # w_down
        P(batch_axes, expert_axis, None),       # x: batch x seq-shard x d
    )
    mapped = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=in_specs,
        out_specs=(P(batch_axes, expert_axis, None), P(), P()),
        check_vma=False)

    def moe_fn(p, x):
        out, aux, drop = mapped(p["router"], p["w_gate"], p["w_up"],
                                p["w_down"], x)
        return out, {"aux_loss": aux, "drop_frac": drop}

    return moe_fn
