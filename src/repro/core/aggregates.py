"""User-defined aggregates — the core MADlib design pattern (§3.1.1, §4.1).

A MADlib method is, at its heart, a ``(init, transition, merge, final)``
quadruple.  The *transition* folds data into a running state, *merge*
combines states from parallel workers (associativity is the parallelization
contract), and *final* turns the merged state into the answer.

TPU adaptation (recorded in DESIGN.md §2): Greenplum feeds the transition
function one tuple at a time; a systolic array wants tiles.  Our transition
contract is **block-at-a-time** — it receives a block of rows ``(B, ...)``
plus a validity mask, so e.g. the OLS ``x xᵀ`` rank-1 update becomes a
``(k, B) @ (B, k)`` MXU matmul (the paper's own v0.3 Eigen lesson, §4.4).

Execution engines provided here:

* :func:`run_local`       — single-shard blocked fold (``lax.scan``).
* :func:`run_sharded`     — ``shard_map`` over the mesh's row axes; local
  fold then mesh-wide merge via ``psum``/``pmax``/``pmin`` (or an
  all-gather fold for non-arithmetic merges).  This is the Greenplum
  segment model, and the engine whose speedup the paper measures.
* :func:`run_stream`      — host-side streaming fold with donated device
  state (the out-of-core path; §2.1's "entire data sets" argument).
* :func:`run_grouped`     — GROUP BY execution (the paper's grouped
  linregr) on the partitioned grouped-scan core: rows are sorted into
  group-aligned blocks once and ALL groups fold in a single O(n) scan
  (:func:`segment_fold`), with a masked-vmap fallback for generic-merge
  aggregates.

Shared-scan composition: :class:`FusedAggregate` packs N heterogeneous
aggregates (each with its own merge combinators, including generic-merge)
into ONE state pytree, so any engine above executes all of them in a
single data pass — the paper's ``profile`` trick (§Table 1: every
column's statistics in one table scan) generalized to arbitrary UDA sets.
:func:`run_many` is the convenience front-end.

Multipass methods wrap these one-pass engines in the unified iterative
executor (:mod:`repro.core.iterative`), which re-executes an aggregate
per driver round under a compiled loop — see ``IterativeTask``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Generic, Iterable, Mapping, TypeVar

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed.sharding import distribute_rows, row_pspec
from . import calibration as _calibration
from .table import GroupedView, Table, Columns
from .trace import record as _record

S = TypeVar("S")  # transition state pytree
R = TypeVar("R")  # result pytree

# Merge combinators, per state leaf.  "sum" covers counts/moments/sketch
# counters; "max"/"min" cover extremes and bitwise-OR over {0,1} bitmaps
# (Flajolet-Martin); "generic" falls back to an all-gather fold using the
# aggregate's own ``merge``.
MERGE_SUM = "sum"
MERGE_MAX = "max"
MERGE_MIN = "min"


class Aggregate:
    """Base class for user-defined aggregates.

    Subclasses implement ``init``/``transition``/``final`` and declare
    ``merge_ops`` — either a single combinator string applied to every state
    leaf, or a pytree of strings matching the state structure.  Aggregates
    whose merge is not expressible leaf-wise override :meth:`merge` and set
    ``merge_ops = None``.
    """

    merge_ops: Any = MERGE_SUM

    # -- registered segment-fold kernel hook ---------------------------------
    # Aggregates with a hand-tiled grouped kernel name it here (a key in
    # kernels/registry.py, e.g. "segment_linregr"); ``kernel_impl`` is the
    # resolved dispatch policy from the method layer's ``use_kernel`` flag
    # (None = inline jnp segment fold, the default).  ``cost_class`` names
    # the calibration bucket the planner prices this aggregate under.
    segment_kernel: str | None = None
    kernel_impl: str | None = None
    cost_class: str = "generic"

    def segment_kernel_args(self, columns: Columns, valid, block_gids,
                            num_groups: int):
        """(args, kwargs) for this aggregate's registered segment kernel —
        pure extraction from the group-aligned layout, so it also runs on
        ``ShapeDtypeStruct`` columns for host-side resolution."""
        raise NotImplementedError

    def segment_kernel_fold(self, columns: Columns, valid, block_gids,
                            num_groups: int, impl: str):
        """Whole-fold (G, ...) state stack via the registered kernel
        (fold-from-zero; the caller merges with the per-group inits)."""
        from ..kernels import registry as _kernels
        args, kwargs = self.segment_kernel_args(columns, valid, block_gids,
                                                num_groups)
        return _kernels.dispatch(self.segment_kernel, *args, impl=impl,
                                 _record=False, **kwargs)

    # -- result-cache identity ----------------------------------------------
    def cache_key(self):
        """Semantic identity of this aggregate for cross-submitter result
        caching (the analytics server): a hashable value that is equal for
        two instances iff they compute the same function of their input —
        i.e. identical finalized results on identical rows.  ``None`` (the
        default) opts out: the statement always executes.  Aggregates
        whose behavior is fully determined by constructor parameters
        should return ``(class tag, *params)``; anything carrying arrays
        or closures in its configuration must stay ``None`` (array-valued
        params have no cheap hashable identity)."""
        return None

    # -- to implement --------------------------------------------------------
    def init(self, block: Columns) -> S:  # block may hold tracers; use shapes only
        raise NotImplementedError

    def transition(self, state: S, block: Columns, mask: jax.Array) -> S:
        raise NotImplementedError

    def final(self, state: S) -> R:
        return state

    # -- default leaf-wise merge ---------------------------------------------
    def merge(self, a: S, b: S) -> S:
        ops = self._merge_ops_tree(a)
        return jax.tree.map(_combine_leaf, ops, a, b)

    def _merge_ops_tree(self, state: S):
        if self.merge_ops is None:
            raise NotImplementedError("generic-merge aggregate must override merge()")
        if isinstance(self.merge_ops, str):
            return jax.tree.map(lambda _: self.merge_ops, state)
        return self.merge_ops

    def segment_ops(self, state: S):
        """Per-leaf merge-combinator tree for segment (scatter) reduction,
        or None when this aggregate is only mergeable through its generic
        ``merge`` and cannot take the partitioned grouped path.  Consult
        AFTER ``init`` has run — schema-templated aggregates (e.g.
        ``ProfileAggregate``) synthesize ``merge_ops`` there."""
        if self.merge_ops is None:
            return None
        return self._merge_ops_tree(state)

    # Mesh-wide merge inside shard_map.
    def mesh_merge(self, state: S, axes: tuple[str, ...]) -> S:
        if self.merge_ops is not None:
            ops = self._merge_ops_tree(state)
            return jax.tree.map(partial(_collective_leaf, axes=axes), ops, state)
        # Generic path: gather every shard's state and fold sequentially.
        return _all_gather_merge_fold(self.merge, state, axes)


def _all_gather_merge_fold(merge_fn, state, axes: tuple[str, ...]):
    """Generic cross-segment merge inside ``shard_map``: all-gather every
    segment's state pytree and fold them sequentially with ``merge_fn``."""
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axes, tiled=False), state
    )
    # leading axis length is the product of the gathered axes
    lead = jax.tree.leaves(gathered)[0].shape[0]
    first = jax.tree.map(lambda x: x[0], gathered)

    def body(i, acc):
        nxt = jax.tree.map(lambda x: x[i], gathered)
        return merge_fn(acc, nxt)

    return jax.lax.fori_loop(1, lead, body, first)


class FusedAggregate(Aggregate):
    """Shared-scan combinator: N aggregates, ONE data pass.

    The fused state is a tuple of the member states; ``transition`` feeds
    the same block/mask to every member, so the engines above fold all of
    them in a single ``lax.scan`` / one ``shard_map`` round instead of N
    table scans.  ``merge``/``mesh_merge`` delegate member-wise, which
    preserves each member's own combinators — sum-merge, min/max-merge and
    generic (all-gather fold) members co-exist in one fused pass.

    ``aggs`` may be a sequence (results come back as a tuple) or a mapping
    (results come back as a dict keyed the same way).
    """

    merge_ops = None  # member-wise delegation; never consulted

    def __init__(self, aggs):
        if isinstance(aggs, Mapping):
            self.names: tuple[str, ...] | None = tuple(aggs)
            self.aggs: tuple[Aggregate, ...] = tuple(aggs[k] for k in self.names)
        else:
            self.names = None
            self.aggs = tuple(aggs)
        if not self.aggs:
            raise ValueError("FusedAggregate needs at least one aggregate")

    def init(self, block):
        return tuple(a.init(block) for a in self.aggs)

    def transition(self, state, block, mask):
        return tuple(a.transition(s, block, mask)
                     for a, s in zip(self.aggs, state))

    def merge(self, a, b):
        return tuple(agg.merge(sa, sb)
                     for agg, sa, sb in zip(self.aggs, a, b))

    def mesh_merge(self, state, axes):
        return tuple(a.mesh_merge(s, axes)
                     for a, s in zip(self.aggs, state))

    def segment_ops(self, state):
        ops = tuple(a.segment_ops(s) for a, s in zip(self.aggs, state))
        if any(o is None for o in ops):
            return None  # one generic-merge member poisons the fused pass
        return ops

    # A single-member fusion (what the plan layer builds for a lone
    # grouped statement) forwards its member's kernel hook, so the fused
    # wrapper doesn't hide the fast path.  Multi-member fusions fold
    # heterogeneous states in one scan — no single kernel covers them.
    @property
    def segment_kernel(self):
        return self.aggs[0].segment_kernel if len(self.aggs) == 1 else None

    @property
    def kernel_impl(self):
        return self.aggs[0].kernel_impl if len(self.aggs) == 1 else None

    @property
    def cost_class(self):
        return self.aggs[0].cost_class if len(self.aggs) == 1 else "generic"

    def segment_kernel_args(self, columns, valid, block_gids, num_groups):
        return self.aggs[0].segment_kernel_args(columns, valid, block_gids,
                                                num_groups)

    def segment_kernel_fold(self, columns, valid, block_gids, num_groups,
                            impl):
        return (self.aggs[0].segment_kernel_fold(
            columns, valid, block_gids, num_groups, impl),)

    def final(self, state):
        outs = tuple(a.final(s) for a, s in zip(self.aggs, state))
        if self.names is not None:
            return dict(zip(self.names, outs))
        return outs


def run_many(aggs, table: Table, *, block_size: int | None = None,
             mask: jax.Array | None = None, jit: bool = True,
             engine: str = "auto", finalize: bool = True,
             trace_kind: str = "scan") -> Any:
    """Execute several aggregates over ``table`` in ONE shared scan.

    ``engine="auto"`` picks the sharded engine when the table is
    distributed, the local one otherwise; ``"local"``/``"sharded"`` force
    one — the hook the plan layer's cost-based selection drives (its
    choice must be what executes, not re-derived here).  Returns a dict
    when ``aggs`` is a mapping, else a tuple, ordered like the input.

    ``finalize=False`` returns the raw fused fold state (a tuple of
    member states) instead of finalized results — the retained-state
    form materialized views pin and later merge with the members' own
    combinators (see :mod:`repro.core.materialize`).
    """
    fused = _fused_for(aggs)
    if engine == "auto":
        engine = "sharded" if table.mesh is not None else "local"
    if engine == "sharded":
        return run_sharded(fused, table, block_size=block_size, mask=mask,
                           jit=jit, finalize=finalize, trace_kind=trace_kind)
    if engine != "local":
        raise ValueError(f"unknown engine {engine!r} "
                         "(use 'auto', 'local' or 'sharded')")
    return run_local(fused, table, block_size=block_size, mask=mask, jit=jit,
                     finalize=finalize, trace_kind=trace_kind)


# Prepared-statement memo: re-executing the same aggregate set reuses
# ONE FusedAggregate instance, so the local engine's program cache
# (static on the aggregate) hits instead of recompiling per call.  Keys
# are member object ids; every entry pins its members, so a live entry's
# ids can never be reused by new objects.  Bounded FIFO.
_FUSED_CACHE: dict[tuple, FusedAggregate] = {}
_FUSED_CACHE_MAX = 256


def _fused_for(aggs) -> FusedAggregate:
    if isinstance(aggs, Mapping):
        key = tuple((k, id(a)) for k, a in aggs.items())
    else:
        key = tuple(id(a) for a in aggs)
    fused = _FUSED_CACHE.get(key)
    if fused is None:
        fused = FusedAggregate(aggs)
        if len(_FUSED_CACHE) >= _FUSED_CACHE_MAX:
            _FUSED_CACHE.pop(next(iter(_FUSED_CACHE)))
        _FUSED_CACHE[key] = fused
    return fused


def _combine_leaf(op: str, a, b):
    if op == MERGE_SUM:
        return a + b
    if op == MERGE_MAX:
        return jnp.maximum(a, b)
    if op == MERGE_MIN:
        return jnp.minimum(a, b)
    raise ValueError(f"unknown merge op {op!r}")


def _collective_leaf(op: str, x, *, axes):
    if op == MERGE_SUM:
        return jax.lax.psum(x, axes)
    if op == MERGE_MAX:
        return jax.lax.pmax(x, axes)
    if op == MERGE_MIN:
        return jax.lax.pmin(x, axes)
    raise ValueError(f"unknown merge op {op!r}")


# ---------------------------------------------------------------------------
# Local (single-shard) blocked fold.
# ---------------------------------------------------------------------------

def _blocked_fold(agg: Aggregate, columns: Columns, mask: jax.Array | None,
                  block_size: int | None) -> Any:
    """Fold ``transition`` over row blocks of ``columns`` on one shard."""
    n = next(iter(columns.values())).shape[0]
    if mask is None:
        mask = jnp.ones((n,), jnp.bool_)
    state = agg.init(columns)
    if block_size is None or block_size >= n:
        return agg.transition(state, columns, mask)

    bs = block_size
    nb = -(-n // bs)  # ceil
    padded = nb * bs
    if padded != n:
        pad = padded - n
        columns = {k: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                   for k, v in columns.items()}
        mask = jnp.pad(mask, (0, pad))

    def step(state, b):
        blk, m = _block_at(columns, mask, b, bs)
        return agg.transition(state, blk, m), None

    state, _ = jax.lax.scan(step, state, jnp.arange(nb))
    return state


def _block_at(columns: Columns, mask: jax.Array, b, bs: int):
    """Row block ``b`` of ``columns`` and ``mask`` by dynamic slice.  An
    ``(nb, bs, ...)`` reshape would make a TPU relayout every
    rows-on-lanes ``(n, k)`` column (see
    :func:`repro.core.table.take_rows`); a slice reads it in place."""
    blk = {k: jax.lax.dynamic_slice_in_dim(v, b * bs, bs)
           for k, v in columns.items()}
    return blk, jax.lax.dynamic_slice_in_dim(mask, b * bs, bs)


# Prepared-statement program cache for the local engine: the jitted pass
# is memoized per aggregate INSTANCE (and block size), so re-executing a
# retained statement — a prepared statement, a driver re-running its
# pass, a bench rep — reuses the compiled program instead of re-tracing.
# Bounded FIFO: evicting an entry drops its jit closure (and with it the
# compiled executable), so one-shot aggregates don't accumulate; a live
# entry pins its aggregate, so ids can't collide.
_LOCAL_JIT_CACHE: dict[tuple, tuple[Aggregate, Callable]] = {}
_LOCAL_JIT_MAX = 256


def _local_jit(agg: Aggregate, block_size, finalize: bool = True):
    key = (id(agg), block_size, finalize)
    hit = _LOCAL_JIT_CACHE.get(key)
    if hit is not None:
        return hit[1]

    def go(columns, mask):
        state = _blocked_fold(agg, columns, mask, block_size)
        return agg.final(state) if finalize else state

    fn = jax.jit(go)
    if len(_LOCAL_JIT_CACHE) >= _LOCAL_JIT_MAX:
        _LOCAL_JIT_CACHE.pop(next(iter(_LOCAL_JIT_CACHE)))
    _LOCAL_JIT_CACHE[key] = (agg, fn)
    return fn


def run_local(agg: Aggregate, table: Table, *, block_size: int | None = None,
              mask: jax.Array | None = None, jit: bool = True,
              finalize: bool = True, trace_kind: str = "scan") -> Any:
    """Execute an aggregate on a single shard (PostgreSQL single-node
    mode).  Compiled programs are reused across calls with the same
    aggregate instance (see ``_LOCAL_JIT_CACHE``).

    ``finalize=False`` returns the raw fold state instead of
    ``agg.final(state)`` — retained states stay mergeable with the
    aggregate's combinators.  ``trace_kind`` labels the recorded event
    ("scan" normally; the materialize layer passes "delta" when this
    pass folds only appended rows)."""
    _record(trace_kind, engine="local", rows=table.n_rows)
    if not jit:
        state = _blocked_fold(agg, dict(table.columns), mask, block_size)
        return agg.final(state) if finalize else state
    return _local_jit(agg, block_size, finalize)(dict(table.columns), mask)


# ---------------------------------------------------------------------------
# Sharded execution (the Greenplum segment model).
# ---------------------------------------------------------------------------

def run_sharded(agg: Aggregate, table: Table, *, mesh: Mesh | None = None,
                row_axes: tuple[str, ...] | None = None,
                block_size: int | None = None,
                mask: jax.Array | None = None, jit: bool = True,
                finalize: bool = True, trace_kind: str = "scan") -> Any:
    """Execute an aggregate in parallel across the mesh's row axes.

    Each shard folds its local rows (transition), states are merged across
    segments with the aggregate's merge combinators (second-phase
    aggregation), and ``final`` runs replicated.  This function is the
    paper's Figure-4 engine.  ``mask`` is a base row filter in table row
    order, sharded alongside the rows and applied at the fold level — the
    same contract as ``run_local``.
    """
    mesh = mesh or table.mesh
    row_axes = tuple(row_axes or table.row_axes or ("data",))
    if mesh is None:
        return run_local(agg, table, block_size=block_size, mask=mask,
                         jit=jit, finalize=finalize, trace_kind=trace_kind)
    _record(trace_kind, engine="sharded", rows=table.n_rows)

    in_spec = jax.tree.map(
        lambda v: row_pspec(row_axes, v.ndim), dict(table.columns)
    )
    if mask is None:
        mask = jnp.ones((table.n_rows,), jnp.bool_)

    def shard_fn(columns, mask):
        local = _blocked_fold(agg, columns, mask, block_size)
        merged = agg.mesh_merge(local, row_axes)
        return agg.final(merged) if finalize else merged

    mapped = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(in_spec, row_pspec(row_axes)),
        out_specs=P(),  # replicated result
        check_vma=False,
    )
    fn = jax.jit(mapped) if jit else mapped
    return fn(dict(table.columns), jnp.asarray(mask))


# ---------------------------------------------------------------------------
# Streaming / out-of-core execution.
# ---------------------------------------------------------------------------

# Same prepared-statement memo for the stream engine's per-block
# programs (step / init-step / final), bounded like _LOCAL_JIT_CACHE.
_STREAM_JIT_CACHE: dict[int, tuple] = {}
_STREAM_JIT_MAX = 128


def _stream_jit(agg: Aggregate):
    hit = _STREAM_JIT_CACHE.get(id(agg))
    if hit is not None:
        return hit[1:]

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, block, mask):
        return agg.transition(state, block, mask)

    @jax.jit
    def init_step(block, mask):
        return agg.transition(agg.init(block), block, mask)

    final = jax.jit(agg.final)
    if len(_STREAM_JIT_CACHE) >= _STREAM_JIT_MAX:
        _STREAM_JIT_CACHE.pop(next(iter(_STREAM_JIT_CACHE)))
    _STREAM_JIT_CACHE[id(agg)] = (agg, step, init_step, final)
    return step, init_step, final


def run_stream(agg: Aggregate, blocks: Iterable[Columns]) -> Any:
    """Fold an aggregate over a host-side stream of row blocks.

    The device-resident state is donated between calls — the analogue of the
    paper's temp-table pattern: all large state stays "in the engine", the
    host only schedules.  Like :func:`run_local`, the per-block programs
    are cached static on the aggregate instance, so re-streaming a
    retained statement re-dispatches compiled steps instead of re-tracing.
    """
    it = iter(blocks)
    try:
        first = next(it)
    except StopIteration:
        raise ValueError("run_stream: empty block stream — at least one "
                         "block is required to seed the fold state") from None
    _record("scan", engine="stream")
    first = {k: jnp.asarray(v) for k, v in first.items()}

    step, init_step, final = _stream_jit(agg)
    n0 = next(iter(first.values())).shape[0]
    state = init_step(first, jnp.ones((n0,), jnp.bool_))
    for block in it:
        block = {k: jnp.asarray(v) for k, v in block.items()}
        n = next(iter(block.values())).shape[0]
        state = step(state, block, jnp.ones((n,), jnp.bool_))
    return final(state)


# ---------------------------------------------------------------------------
# GROUP BY execution — the partitioned grouped-scan core.
# ---------------------------------------------------------------------------

# Default row-block size for the segment path: bounds the (block, state)
# per-row intermediates the singleton transitions materialize.
_SEGMENT_BLOCK = 4096


def _scatter_leaf(op: str, acc, idx, vals):
    """Segment-merge one state leaf: fold the per-row states ``vals``
    (leading row axis, aligned with segment ids ``idx``) into the
    per-group accumulator ``acc`` with the leaf's merge combinator."""
    if op == MERGE_SUM:
        return acc.at[idx].add(vals)
    if op == MERGE_MAX:
        return acc.at[idx].max(vals)
    if op == MERGE_MIN:
        return acc.at[idx].min(vals)
    raise ValueError(f"unknown merge op {op!r}")


def probe_segment_ops(agg: Aggregate, columns: Columns):
    """Merge-combinator tree of ``agg`` over ``columns``' schema, or None
    when the aggregate is not segment-reducible (generic merge).  Runs
    ``init`` abstractly so schema-templated aggregates synthesize their
    ops without touching data."""
    spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in columns.items()}
    state_s = jax.eval_shape(agg.init, spec)
    return agg.segment_ops(state_s)


def segment_block_size(n_rows: int, num_groups: int,
                       block_size: int | None = None) -> int:
    """Block size for the group-aligned layout: near the average segment
    (padding overhead is one partial block per group), power-of-two,
    clamped to [64, _SEGMENT_BLOCK].  An explicit ``block_size`` wins;
    an ACTIVE measured calibration's best block for this shape bucket
    beats the heuristic (see :mod:`repro.core.calibration`)."""
    if block_size is not None:
        return max(1, int(block_size))
    cal = _calibration.current()
    if cal is not None:
        b = cal.grouped_block_size(n_rows, num_groups)
        if b:
            return max(1, int(b))
    avg = max(1, -(-n_rows // max(1, num_groups)))
    return max(64, min(_SEGMENT_BLOCK, 1 << (avg - 1).bit_length()))


def segment_block_update(make_agg, group_states, ops, blk: Columns,
                         bm: jax.Array, g: jax.Array, acc) -> Any:
    """Fold ONE group-aligned block into the stacked per-group
    accumulators: run the (possibly group-parameterized) aggregate's real
    block transition from init, then scatter-merge the block state into
    group ``g``'s slot with each leaf's combinator.  Shared by the
    one-pass scan (:func:`segment_fold`) and the iterative engine's
    compacted block loop — the single definition of the segment-merge
    contract."""
    s_g = jax.tree.map(lambda s: s[g], group_states)
    a = make_agg(s_g)
    bstate = a.transition(a.init(blk), blk, bm)
    return jax.tree.map(
        lambda op, al, bl: _scatter_leaf(op, al, g[None], bl[None]),
        ops, acc, bstate)


def segment_fold(make_agg, group_states, ops, columns: Columns,
                 valid: jax.Array, block_gids: jax.Array,
                 num_groups: int, *, agg: Aggregate | None = None,
                 kernel_impl: str | None = None) -> Any:
    """Fold EVERY group's state in ONE O(n) blocked scan (jit-traceable).

    Consumes the group-aligned layout of
    :meth:`~repro.core.table.GroupedView.aligned_blocks`: each block holds
    rows of exactly one group, so the aggregate's REAL block transition
    runs per block (the same MXU-shaped update as the solo fold, with
    padding rows masked out) and the block state is segment-merged into
    the stacked ``(num_groups, ...)`` accumulators with each leaf's merge
    combinator (``ops``, from :meth:`Aggregate.segment_ops`).  Correctness
    rests on exactly the contract :func:`run_sharded` already imposes:
    folding a row partition from init and merging leaf-wise must equal the
    sequential fold, with init the merge identity (so empty groups keep
    their init state).

    ``make_agg(state_g)`` builds the (possibly per-group-parameterized)
    aggregate; pass ``lambda _: agg`` with dummy states for a uniform
    aggregate.

    ``agg`` + ``kernel_impl`` engage the aggregate's registered
    segment-fold kernel (resolved host-side, see
    :func:`_resolve_segment_kernel`): the whole fold runs as ONE fused
    Pallas grid loop (or its jnp ref oracle) computing the fold-from-zero
    state stack, then merges with the vmapped per-group inits under the
    leaf combinators — bit-identical to the generic scan for exact-state
    aggregates because init is the merge identity.
    """
    lead = jax.tree.leaves(group_states)[0].shape[0]
    if lead != num_groups:
        raise ValueError(f"segment_fold: group_states lead axis {lead} "
                         f"!= num_groups={num_groups}")
    inits = jax.vmap(lambda s: make_agg(s).init(columns))(group_states)
    nb = block_gids.shape[0]
    if nb == 0:
        return inits
    if kernel_impl is not None and agg is not None \
            and getattr(agg, "segment_kernel", None):
        kstates = agg.segment_kernel_fold(columns, valid, block_gids,
                                          num_groups, kernel_impl)
        return jax.tree.map(_combine_leaf, ops, inits, kstates)
    n2 = next(iter(columns.values())).shape[0]
    bs = n2 // nb

    def step(acc, b):
        blk, bm = _block_at(columns, valid, b, bs)
        return segment_block_update(make_agg, group_states, ops, blk, bm,
                                    block_gids[b], acc), None

    acc, _ = jax.lax.scan(step, inits, jnp.arange(nb))
    return acc


def merge_group_states(agg: Aggregate, ops, states, axes: tuple[str, ...]):
    """Cross-segment merge of stacked ``(G, ...)`` per-group states inside
    ``shard_map``: leaf-wise collectives when the aggregate declares merge
    combinators (``ops`` from :meth:`Aggregate.segment_ops`), else an
    all-gather of every segment's group-state stack folded with the
    aggregate's own generic ``merge`` (vmapped over the group axis)."""
    if ops is not None:
        return jax.tree.map(partial(_collective_leaf, axes=axes), ops,
                            states)
    return _all_gather_merge_fold(jax.vmap(agg.merge), states, axes)


def _mesh_segments(mesh: Mesh, row_axes: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in row_axes]))


# Prepared-statement memo for the local segment path, keyed like
# _LOCAL_JIT_CACHE (the jit object retraces by itself when block shapes
# change, so block size is not part of the key).  Without it every
# grouped pass re-traced from scratch — a fixed per-call cost that
# dwarfed small folds such as a living view's delta refresh.
_SEGMENT_JIT_CACHE: dict[tuple, tuple[Aggregate, Callable]] = {}
_SEGMENT_JIT_MAX = 256


def _resolve_segment_kernel(agg: Aggregate, columns, valid, bgids,
                            num_groups: int) -> str | None:
    """Host-side kernel resolution for ONE physical grouped execution:
    which implementation of the aggregate's registered segment kernel
    runs (``"ref"``/``"pallas"``), or None for the inline jnp segment
    fold (no kernel requested).  Runs the registry's resolve on the
    concrete shapes (``ShapeDtypeStruct`` works) BEFORE tracing, so a
    forced ``impl="pallas"`` an unsupported backend/shape cannot take
    fails loudly here, and records the resolved impl on active traces —
    once per execution, not per traced dispatch."""
    name = getattr(agg, "segment_kernel", None)
    impl = getattr(agg, "kernel_impl", None)
    if name is None or impl is None:
        return None
    from ..kernels import registry as _kernels
    args, kwargs = agg.segment_kernel_args(columns, valid, bgids, num_groups)
    resolved, _tuned = _kernels.get(name).resolve(impl, *args, **kwargs)
    _record("kernel", engine=resolved, name=name, requested=impl)
    return resolved


def _segment_jit(agg: Aggregate, ops, G: int, finalize: bool, schema,
                 seg_impl: str | None = None):
    # schema is part of the key because templated aggregates derive their
    # state tree (and thus ops) from the column set, not just the
    # instance; seg_impl because the resolved kernel changes the program
    key = (id(agg), G, finalize, schema, seg_impl)
    hit = _SEGMENT_JIT_CACHE.get(key)
    if hit is not None:
        return hit[1]
    dummy_states = jnp.zeros((G,), jnp.int32)
    group_final = jax.vmap(agg.final) if finalize else (lambda s: s)

    def go_segment(columns, valid, bgids):
        states = segment_fold(lambda _s: agg, dummy_states, ops,
                              columns, valid, bgids, G,
                              agg=agg, kernel_impl=seg_impl)
        return group_final(states)

    fn = jax.jit(go_segment)
    if len(_SEGMENT_JIT_CACHE) >= _SEGMENT_JIT_MAX:
        _SEGMENT_JIT_CACHE.pop(next(iter(_SEGMENT_JIT_CACHE)))
    _SEGMENT_JIT_CACHE[key] = (agg, fn)
    return fn


def run_grouped(agg: Aggregate, table, group_col: str | None = None,
                num_groups: int | None = None, *,
                block_size: int | None = None,
                mask: jax.Array | None = None,
                method: str = "auto", mesh: Mesh | None = None,
                row_axes: tuple[str, ...] | None = None,
                jit: bool = True, finalize: bool = True,
                trace_kind: str = "scan") -> Any:
    """Grouped aggregation (``SELECT ..., agg(...) GROUP BY g``).

    ``table`` is either a :class:`Table` — grouped by its ``group_col``
    column — or a prebuilt :class:`~repro.core.table.GroupedView`
    (``group_col`` ignored), so multi-pass grouped methods pay the
    partitioning sort once and share it across scans.  Star-schema
    joined aggregation reaches this engine UNCHANGED: the join layer
    (:mod:`repro.core.join`) resolves ``fact JOIN dim`` to a fact-
    aligned integer group-id column and this function grouped-scans it
    like any other key — out-of-range ids (``-1`` for dropped dangling
    foreign keys) fall outside every segment by :meth:`Table.group_by`'s
    documented semantics.

    Two execution strategies share the engine:

    * ``method="segment"`` — the partitioned grouped-scan core: rows are
      permuted into group-aligned blocks once (:meth:`Table.group_by` +
      ``aligned_blocks``) and ALL groups fold in a single O(n) blocked
      scan with a per-block segment merge (:func:`segment_fold`).
      Requires leaf-wise merge combinators (``agg.segment_ops``).
    * ``method="masked"`` — the fallback for generic-merge aggregates:
      vmap the blocked masked fold over group ids; every group scans the
      full table (O(G·n)), exact for any aggregate honoring the mask
      contract.

    ``method="auto"`` picks segment whenever the aggregate supports it.
    ``mask`` is a base row filter applied before grouping (like
    ``run_local``), always given in the ORIGINAL table's row order;
    ``num_groups`` defaults to ``max(gid) + 1`` (the view's group count).

    ``mesh`` (defaulting to the table's) engages the SHARDED grouped
    engine — MADlib's two-phase GROUP BY (§4.1) across the mesh's row
    axes: the group-aligned blocks are distributed in whole-block chunks,
    every segment runs the real per-block transition locally
    (:func:`segment_fold` on its chunk), and the G per-segment partial
    states merge with each leaf's combinator collective — one data pass,
    ``G x num_segments`` partial states, bit-identical to the local
    segment fold for exact-state aggregates.  Generic-merge aggregates
    take a sharded masked path instead (local masked folds, all-gather
    generic merge).

    ``finalize=False`` returns the stacked ``(G, ...)`` fold states
    instead of ``vmap(final)`` results (the retained form materialized
    grouped views merge group-wise); ``trace_kind`` labels the recorded
    event as in :func:`run_local`.
    """
    view = table if isinstance(table, GroupedView) else None
    base_tbl = view.table if view is not None else table
    if mesh is None:
        mesh = base_tbl.mesh
    row_axes = tuple(row_axes or base_tbl.row_axes or ("data",))
    if view is not None:
        if num_groups is not None and num_groups != view.num_groups:
            raise ValueError(f"run_grouped: num_groups={num_groups} "
                             f"disagrees with the view's {view.num_groups}")
        num_groups = view.num_groups
        data = dict(view.table.columns)
    else:
        if group_col is None:
            raise ValueError("run_grouped: group_col is required when "
                             "grouping a Table (or pass a GroupedView)")
        if num_groups is None:
            num_groups = int(jax.device_get(
                jnp.max(table[group_col].astype(jnp.int32)))) + 1
        data = {k: v for k, v in table.columns.items() if k != group_col}
    G = num_groups

    if method in ("auto", "segment"):
        ops = probe_segment_ops(agg, data)
    elif mesh is not None:
        # forced masked + sharded: ops only optimize the cross-shard
        # merge, so an un-probe-able init (abstract-eval failure in a
        # generic-merge aggregate) must not be fatal
        try:
            ops = probe_segment_ops(agg, data)
        except Exception:
            ops = None
    else:
        ops = None  # forced masked, local: ops never consulted
    if method == "auto":
        method = "segment" if ops is not None else "masked"
    _record(trace_kind, engine=f"grouped-{method}", sharded=mesh is not None,
            groups=G)
    group_final = jax.vmap(agg.final) if finalize else (lambda s: s)

    if method == "segment":
        if ops is None:
            raise ValueError(
                "run_grouped: method='segment' needs leaf-wise merge "
                "combinators (agg.segment_ops() returned None); use "
                "method='masked' for generic-merge aggregates")
        if view is None:
            view = table.group_by(group_col, G)
        pmask = None if mask is None else view.permute(mask)
        bs = segment_block_size(view.n_rows, G, block_size)
        dummy_states = jnp.zeros((G,), jnp.int32)

        if mesh is None:
            cols_a, valid_a, bgids = view.aligned_blocks(bs, pmask)
            seg_impl = _resolve_segment_kernel(agg, cols_a, valid_a,
                                               bgids, G)
            if jit:
                schema = tuple(sorted(
                    (k, str(v.dtype), tuple(v.shape[1:]))
                    for k, v in data.items()))
                return _segment_jit(agg, ops, G, finalize, schema,
                                    seg_impl)(cols_a, valid_a, bgids)

            def go_segment(columns, valid, bgids):
                states = segment_fold(lambda _s: agg, dummy_states, ops,
                                      columns, valid, bgids, G,
                                      agg=agg, kernel_impl=seg_impl)
                return group_final(states)

            return go_segment(cols_a, valid_a, bgids)

        # Sharded segment path: each segment folds its local chunk of
        # group-aligned blocks, per-group partials merge leaf-wise.
        cols_a, valid_a, bgids = view.sharded_blocks(mesh, row_axes, bs,
                                                     pmask)
        # kernel resolution sees the SHARD-LOCAL shapes the kernel will
        # run on inside shard_map (sharded_blocks pads every segment to
        # whole blocks, so the division is exact)
        segs = _mesh_segments(mesh, row_axes)
        _local = lambda v: jax.ShapeDtypeStruct(
            (v.shape[0] // segs,) + v.shape[1:], v.dtype)
        seg_impl = _resolve_segment_kernel(
            agg, jax.tree.map(_local, dict(cols_a)), _local(valid_a),
            _local(bgids), G)
        in_spec = jax.tree.map(
            lambda v: row_pspec(row_axes, v.ndim), cols_a)

        def shard_segment(columns, valid, bgids):
            states = segment_fold(lambda _s: agg, dummy_states, ops,
                                  columns, valid, bgids, G,
                                  agg=agg, kernel_impl=seg_impl)
            merged = merge_group_states(agg, ops, states, row_axes)
            return group_final(merged)

        mapped = jax.shard_map(
            shard_segment, mesh=mesh,
            in_specs=(in_spec, row_pspec(row_axes), row_pspec(row_axes)),
            out_specs=P(), check_vma=False)
        fn = jax.jit(mapped) if jit else mapped
        return fn(cols_a, valid_a, bgids)

    if method != "masked":
        raise ValueError(f"unknown method {method!r} "
                         "(use 'auto', 'segment' or 'masked')")

    if view is not None:
        gids = view.gids
        base_mask = None if mask is None else view.permute(mask)
    else:
        gids = table[group_col].astype(jnp.int32)
        base_mask = mask

    if mesh is not None:
        return _run_grouped_masked_sharded(
            agg, ops, data, gids, base_mask, G, block_size, mesh, row_axes,
            jit, group_final)

    def go_masked(data, gids, mask):
        base = jnp.ones(gids.shape, jnp.bool_) if mask is None else mask

        def per_group(g):
            return _blocked_fold(agg, data, (gids == g) & base, block_size)

        return group_final(jax.vmap(per_group)(jnp.arange(G)))

    fn = jax.jit(go_masked) if jit else go_masked
    return fn(data, gids, base_mask)


def _run_grouped_masked_sharded(agg, ops, data, gids, base_mask, G,
                                block_size, mesh, row_axes, jit_,
                                group_final):
    """Sharded masked path: every segment folds its LOCAL rows once per
    group (mask contract), per-group partial states merge across segments
    — leaf-wise collectives when available, the all-gather generic fold
    otherwise.  Rows are padded (masked invalid) to divide the segment
    count, so any local table works with an explicit ``mesh=``."""
    segs = _mesh_segments(mesh, row_axes)
    n = next(iter(data.values())).shape[0]
    valid = jnp.ones((n,), jnp.bool_) if base_mask is None \
        else jnp.asarray(base_mask)
    pad = -n % segs
    if pad:
        data = {k: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
                for k, v in data.items()}
        gids = jnp.pad(gids, (0, pad), constant_values=-1)
        valid = jnp.pad(valid, (0, pad))  # padding rows: invalid
    placed = distribute_rows(mesh, row_axes,
                             dict(data, __gid__=gids, __valid__=valid))
    gids = placed.pop("__gid__")
    valid = placed.pop("__valid__")
    in_spec = jax.tree.map(lambda v: row_pspec(row_axes, v.ndim), placed)

    def shard_masked(data, gids, valid):
        def per_group(g):
            return _blocked_fold(agg, data, (gids == g) & valid, block_size)

        states = jax.vmap(per_group)(jnp.arange(G))
        merged = merge_group_states(agg, ops, states, row_axes)
        return group_final(merged)

    mapped = jax.shard_map(
        shard_masked, mesh=mesh,
        in_specs=(in_spec, row_pspec(row_axes), row_pspec(row_axes)),
        out_specs=P(),
        check_vma=False)
    fn = jax.jit(mapped) if jit_ else mapped
    return fn(placed, gids, valid)
