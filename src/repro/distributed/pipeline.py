"""Pipeline parallelism (GPipe schedule) over ``collective_permute``.

Completes the DP/TP/PP/EP/SP matrix (DESIGN.md §6): stages are laid out
along a mesh axis (the "pod" axis in the production meshes — pipeline
stages across pods keep the high-volume within-stage collectives on fast
intra-pod ICI and move only (microbatch × d_model) activations across
the slow inter-pod links, once per microbatch per boundary).

Schedule: classic GPipe fill-drain over M microbatches and S stages —
``M + S − 1`` ticks; at each tick every stage runs its block on the
activation it received last tick and forwards the result one stage down
via ``collective_permute``.  Bubble fraction (S−1)/(M+S−1) is reported
by :func:`bubble_fraction` so launch configs can size M.

The stage function is arbitrary (a layer stack); parameters come in
stacked over the stage axis and shard_map slices them per stage.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def make_pipeline(mesh: Mesh, stage_fn, *, stage_axis: str = "pod",
                  n_microbatches: int | None = None):
    """Returns pipe(params_stacked, x) -> y.

    ``params_stacked``: pytree with leading axis = n_stages (sharded over
    ``stage_axis``).  ``x``: (M, mb, ...) microbatched input, replicated
    over the stage axis.  ``stage_fn(params, act) -> act`` must preserve
    the activation shape (a residual-block stack does).
    """
    n_stages = mesh.shape[stage_axis]

    def shard_fn(params, x):
        # params: (1, ...) local stage slice; x: (M, mb, ...)
        local = jax.tree.map(lambda p: p[0], params)
        m = x.shape[0]
        stage = jax.lax.axis_index(stage_axis)
        ticks = m + n_stages - 1
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (others use what they received)
            inject = jnp.where(t < m, t, m - 1)
            mb_in = jax.lax.dynamic_index_in_dim(x, inject, keepdims=False)
            act = jnp.where(stage == 0, mb_in, buf)
            act = stage_fn(local, act)
            # last stage writes its finished microbatch (valid once the
            # pipe has filled: tick >= stage index of last stage)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (out_idx < m)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, act, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # forward activations one stage down the chain
            buf = jax.lax.ppermute(act, stage_axis, fwd_perm)
            return (buf, outs), None

        buf0 = jnp.zeros_like(x[0])
        outs0 = jnp.zeros_like(x)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them back
        # (masked psum — ppermute requires unique source/dest pairs)
        mask = (stage == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, stage_axis)

    def pipe(params_stacked, x):
        pspec = jax.tree.map(lambda _: P(stage_axis), params_stacked)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec, P()),
            out_specs=P(),
            check_vma=False,
        )(params_stacked, x)

    return pipe
