"""Unified iterative executor — ONE driver loop for every multipass method.

MADlib's §3.1.2 driver pattern (a state-resident outer loop around a bulk
UDA inner pass) used to be reimplemented per method: ``logregr`` IRLS,
``kmeans`` Lloyd, ``lda`` EM and the ``convex`` solvers each hand-rolled
their own convergence loop.  Following Feng et al.'s *Towards a Unified
Architecture for in-RDBMS Analytics* (Bismarck), they all fit one harness:

    state_0 = init ;  repeat:  agg_out = ONE shared scan (a UDA pass)
                               state   = update(state, agg_out)   # driver
                               m       = metric(...)              # scalar
              until m < tol or max_iters

The **task contract** is :class:`IterativeTask`:

* ``init_state(columns)``   — driver-side model state (small, device-resident)
* ``make_aggregate(state)`` — the per-iteration UDA pass, any
  :class:`~repro.core.aggregates.Aggregate` (use ``FusedAggregate`` to fold
  several statistics in the same scan)
* ``update(state, agg_out)``— the driver-side step (solve, renormalize, …)
* ``metric(prev, new, agg_out)`` — scalar convergence criterion (< tol stops)
* ``finalize(state, agg_out)``   — shape the last state/pass into the result
* ``trace_record(state, agg_out, m)`` — small per-iteration record (traced)

Tasks whose iteration is not a single pure scan (two-pass k-means, SGD
epochs) override :meth:`IterativeTask.iteration` instead and call the
supplied ``run_pass`` runner as many times as their dataflow needs — the
controller still owns the loop, the engines and convergence.

**One controller, four engines.**  :func:`fit` executes any task

* locally (single shard, blocked ``lax.scan`` fold),
* sharded (the whole loop lives inside ONE ``shard_map`` program: local
  fold → ``psum``-family merge → replicated update, per iteration — zero
  host round-trips across the entire fit),
* streaming (:func:`fit_stream`: each iteration re-folds a host-side
  block stream with donated device state — the out-of-core path), and
* grouped (:func:`fit_grouped`: ``GROUP BY`` model fitting — one model
  per group, every iteration a shared scan over the whole table with
  per-group masks, converged groups frozen).

``mode="compiled"`` (default) turns the loop into a single
``lax.while_loop`` (or ``lax.scan`` when ``tol=None`` — fixed-count
iteration); ``mode="host"`` keeps a Python loop that pulls one scalar per
round (the paper-faithful driver, useful for debugging and for streams).
New methods should register a task here instead of writing loops:
``grep "for it in range" src/repro/methods`` is expected to stay empty.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import row_pspec
from .aggregates import (
    Aggregate, _block_at, _blocked_fold, _collective_leaf, probe_segment_ops,
    run_local, run_sharded, run_stream, segment_block_size,
    segment_block_update,
)
from .table import Table, Columns
from .trace import record as _record


def relative_change(prev, new) -> jax.Array:
    """Default convergence metric: ||new - prev|| / (||prev|| + eps)."""
    dn = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda p, n: jnp.sum((n - p) ** 2), prev, new),
    )
    pn = jax.tree.reduce(
        lambda a, b: a + b, jax.tree.map(lambda p: jnp.sum(p ** 2), prev)
    )
    return jnp.sqrt(dn) / (jnp.sqrt(pn) + 1e-12)


# ---------------------------------------------------------------------------
# Pass runners — how one UDA pass executes under each engine.
# ---------------------------------------------------------------------------

class PassRunner:
    """Executes ONE shared scan inside a compiled engine.

    ``columns``/``mask`` expose the engine-local rows to tasks that are not
    pure folds (e.g. SGD epochs, which gather shuffled minibatches);
    ``row_axes`` is non-empty exactly when running inside ``shard_map`` —
    such tasks must merge their own state across segments (``pmean``/...).
    """

    def __init__(self, columns: Columns, mask=None,
                 block_size: int | None = None,
                 row_axes: tuple[str, ...] = ()):
        self.columns = columns
        self.mask = mask
        self.block_size = block_size
        self.row_axes = tuple(row_axes)

    def __call__(self, agg: Aggregate):
        local = _blocked_fold(agg, self.columns, self.mask, self.block_size)
        if self.row_axes:
            local = agg.mesh_merge(local, self.row_axes)
        return agg.final(local)


class _EagerRunner:
    """Host-mode runner: one jitted engine call per pass (run_local /
    run_sharded pick the engine from the table's distribution)."""

    row_axes: tuple[str, ...] = ()

    def __init__(self, table: Table, mask=None, block_size: int | None = None):
        self.table = table
        self.columns = dict(table.columns)
        self.mask = mask
        self.block_size = block_size

    def __call__(self, agg: Aggregate):
        if self.table.mesh is not None:
            return run_sharded(agg, self.table, block_size=self.block_size,
                               mask=self.mask)
        return run_local(agg, self.table, block_size=self.block_size,
                         mask=self.mask)


class _StreamRunner:
    """Each pass re-folds a fresh block stream; state stays on device."""

    row_axes: tuple[str, ...] = ()
    columns = None
    mask = None

    def __init__(self, blocks_factory: Callable[[], Iterable[Columns]]):
        self.blocks_factory = blocks_factory

    def __call__(self, agg: Aggregate):
        return run_stream(agg, self.blocks_factory())


# ---------------------------------------------------------------------------
# The task protocol.
# ---------------------------------------------------------------------------

class IterativeTask:
    """Base class for iterative fits (see module docstring for the contract).

    Subclasses implement ``init_state`` / ``make_aggregate`` / ``update``
    (and usually ``metric`` / ``finalize``); tasks whose iteration is not a
    single scan override :meth:`iteration`.
    """

    def init_state(self, columns: Columns) -> Any:
        raise NotImplementedError

    def make_aggregate(self, state) -> Aggregate:
        raise NotImplementedError

    def update(self, state, agg_out) -> Any:
        raise NotImplementedError

    def metric(self, prev_state, new_state, agg_out) -> jax.Array:
        return relative_change(prev_state, new_state)

    def finalize(self, state, agg_out) -> Any:
        return state

    def trace_record(self, state, agg_out, metric) -> Any:
        return metric

    def mesh_epilogue(self, state, row_axes: tuple[str, ...]) -> Any:
        """Sharded-engine hook, applied once after the loop (still inside
        ``shard_map``): bring a per-segment final state to a replicated
        one.  Identity for tasks whose carry is already replicated (every
        pure-UDA task); tasks that defer their cross-segment merge (e.g.
        one-shot model averaging) override this."""
        return state

    def iteration(self, state, run_pass) -> tuple[Any, Any, jax.Array]:
        """One driver round: (new_state, agg_out, metric).  Override for
        multi-statement iterations; call ``run_pass(aggregate)`` once per
        data pass your dataflow needs."""
        out = run_pass(self.make_aggregate(state))
        new = self.update(state, out)
        return new, out, self.metric(state, new, out)


@dataclasses.dataclass
class FitResult:
    """Outcome of an iterative fit.

    ``state`` is the final driver state, ``result`` is
    ``task.finalize(state, last agg_out)``.  ``trace`` is the pytree of
    stacked per-iteration :meth:`IterativeTask.trace_record` values (leading
    axis = iterations actually run; for grouped fits the group axis leads).
    ``n_iters``/``converged`` are scalars — per-group vectors for
    :func:`fit_grouped`.  ``stats`` carries engine diagnostics (grouped
    fits record the layout, per-round active-row counts and total row
    blocks scanned); None for engines that report nothing.
    """

    state: Any
    result: Any
    n_iters: Any
    converged: Any
    trace: Any
    stats: Any = None


# ---------------------------------------------------------------------------
# Compiled loop bodies (absorbing core/driver.py's engines).
# ---------------------------------------------------------------------------

def _zeros_of(struct):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def _cast_like(tree, struct):
    return jax.tree.map(lambda x, s: jnp.asarray(x, s.dtype), tree, struct)


def _make_iter_fn(task: IterativeTask, runner):
    def iter_fn(state):
        new, aux, m = task.iteration(state, runner)
        rec = task.trace_record(new, aux, m)
        return new, aux, jnp.asarray(m, jnp.float32), rec
    return iter_fn


def _while_fit(iter_fn, state0, max_iters: int, tol: float):
    """``lax.while_loop`` fast path: the convergence test is part of the
    compiled program (data-dependent stopping, zero host round-trips)."""
    state0 = jax.tree.map(jnp.asarray, state0)
    state_s = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state0)
    _, aux_s, _, rec_s = jax.eval_shape(iter_fn, state0)
    trace0 = jax.tree.map(
        lambda s: jnp.zeros((max_iters,) + s.shape, s.dtype), rec_s)

    def cond(c):
        _, _, i, m, _ = c
        return jnp.logical_and(i < max_iters, m >= tol)

    def body(c):
        state, _, i, _, trace = c
        new, aux, m, rec = iter_fn(state)
        trace = jax.tree.map(lambda t, r: t.at[i].set(r), trace,
                             _cast_like(rec, rec_s))
        return (_cast_like(new, state_s), _cast_like(aux, aux_s), i + 1, m,
                trace)

    init = (state0, _zeros_of(aux_s), jnp.int32(0), jnp.float32(jnp.inf),
            trace0)
    return jax.lax.while_loop(cond, body, init)


def _scan_fit(iter_fn, state0, n_iters: int):
    """``lax.scan`` fast path for fixed-count iteration (``tol=None``)."""
    state0 = jax.tree.map(jnp.asarray, state0)
    state_s = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state0)
    _, aux_s, _, rec_s = jax.eval_shape(iter_fn, state0)

    def step(carry, _):
        state, _ = carry
        new, aux, m, rec = iter_fn(state)
        return (_cast_like(new, state_s), _cast_like(aux, aux_s)), \
            _cast_like(rec, rec_s)

    (state, aux), trace = jax.lax.scan(
        step, (state0, _zeros_of(aux_s)), None, length=n_iters)
    return state, aux, jnp.int32(n_iters), jnp.float32(jnp.inf), trace


# ---------------------------------------------------------------------------
# The controller.
# ---------------------------------------------------------------------------

def fit(task: IterativeTask, table: Table, *, max_iters: int = 100,
        tol: float | None = 1e-6, engine: str = "auto",
        mode: str = "compiled", block_size: int | None = None,
        mask: jax.Array | None = None, warm_start: Any = None,
        mesh=None, row_axes=None, jit: bool = True) -> FitResult:
    """Execute an :class:`IterativeTask` to convergence on one engine.

    ``engine``: "auto" (sharded iff the table is distributed), "local", or
    "sharded".  ``tol=None`` runs exactly ``max_iters`` rounds (``lax.scan``).
    ``warm_start`` seeds the driver state (skips ``task.init_state``).
    """
    if engine not in ("auto", "local", "sharded"):
        raise ValueError(f"unknown engine {engine!r} (use 'auto', 'local' "
                         "or 'sharded'; streaming goes through fit_stream)")
    columns = dict(table.columns)
    mesh = mesh if mesh is not None else table.mesh
    row_axes = tuple(row_axes or table.row_axes or ("data",))
    if engine == "auto":
        engine = "sharded" if mesh is not None else "local"
    if engine == "sharded" and mesh is None:
        engine = "local"

    state0 = warm_start if warm_start is not None else task.init_state(columns)
    state0 = jax.tree.map(jnp.asarray, state0)
    _record("fit", engine=engine, mode=mode)

    if mode == "host":
        return _fit_host(task, table, mask, state0, block_size, max_iters,
                         tol)
    if mode != "compiled":
        raise ValueError(f"unknown mode {mode!r}")

    if engine == "local":
        def go(columns, mask, state0):
            runner = PassRunner(columns, mask, block_size)
            iter_fn = _make_iter_fn(task, runner)
            if tol is None:
                return _scan_fit(iter_fn, state0, max_iters)
            return _while_fit(iter_fn, state0, max_iters, tol)

        fn = jax.jit(go) if jit else go
        state, aux, n, m, trace = fn(columns, mask, state0)
    else:
        in_spec = jax.tree.map(
            lambda v: row_pspec(row_axes, v.ndim), columns)
        mask_arr = jnp.ones((table.n_rows,), jnp.bool_) if mask is None \
            else jnp.asarray(mask)

        def shard_fn(columns, mask, state0):
            runner = PassRunner(columns, mask, block_size, row_axes)
            iter_fn = _make_iter_fn(task, runner)
            if tol is None:
                out = _scan_fit(iter_fn, state0, max_iters)
            else:
                out = _while_fit(iter_fn, state0, max_iters, tol)
            state, aux, n, m, trace = out
            return task.mesh_epilogue(state, row_axes), aux, n, m, trace

        mapped = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(in_spec, row_pspec(row_axes), P()),
            out_specs=P(), check_vma=False)
        fn = jax.jit(mapped) if jit else mapped
        state, aux, n, m, trace = fn(columns, mask_arr, state0)

    result = task.finalize(state, aux)
    n = int(n)
    converged = False if tol is None else bool(m < tol)
    trace = jax.tree.map(lambda t: np.asarray(t[:n]), trace)
    return FitResult(state, result, n, converged, trace)


def _host_loop(task, runner, state0, max_iters, tol) -> FitResult:
    """Paper-faithful host driver: one engine call per pass, one scalar
    (the metric) pulled to the host per round."""
    state = state0
    aux = None
    recs = []
    converged = False
    n = 0
    for n in range(1, max_iters + 1):
        state, aux, m = task.iteration(state, runner)
        recs.append(task.trace_record(state, aux, m))
        if tol is not None and float(m) < tol:
            converged = True
            break
    trace = jax.tree.map(lambda *xs: np.asarray(jnp.stack(xs)), *recs)
    return FitResult(state, task.finalize(state, aux), n, converged, trace)


def _fit_host(task, table, mask, state0, block_size, max_iters, tol):
    return _host_loop(task, _EagerRunner(table, mask, block_size), state0,
                      max_iters, tol)


def fit_stream(task: IterativeTask,
               blocks_factory: Callable[[], Iterable[Columns]], *,
               max_iters: int = 100, tol: float | None = 1e-6,
               warm_start: Any = None) -> FitResult:
    """Out-of-core iteration: every round streams the blocks produced by a
    fresh ``blocks_factory()`` through :func:`run_stream` (device-resident
    fold state), so only one block is ever materialized on device."""
    if warm_start is not None:
        state0 = jax.tree.map(jnp.asarray, warm_start)
    else:
        try:
            first = next(iter(blocks_factory()))
        except StopIteration:
            raise ValueError("fit_stream: blocks_factory() produced no "
                             "blocks — at least one block is required to "
                             "shape the driver state") from None
        state0 = jax.tree.map(
            jnp.asarray,
            task.init_state({k: jnp.asarray(v) for k, v in first.items()}))
    _record("fit", engine="stream")
    return _host_loop(task, _StreamRunner(blocks_factory), state0,
                      max_iters, tol)


# ---------------------------------------------------------------------------
# GROUP BY model fitting — one model per group, shared scans.
# ---------------------------------------------------------------------------

def fit_grouped(task: IterativeTask, table: Table, key_col: str,
                num_groups: int | None = None, *, max_iters: int = 100,
                tol: float | None = 1e-6, block_size: int | None = None,
                mask: jax.Array | None = None, warm_start: Any = None,
                layout: str = "auto", mesh=None, row_axes=None,
                jit: bool = True) -> FitResult:
    """Fit one model per group of ``key_col`` — MADlib's ``GROUP BY``
    model fitting (the paper's grouped linregr, §4.1) generalized to every
    registered task.

    Two layouts share the controller:

    * ``layout="segment"`` — the partitioned grouped-scan core: rows are
      permuted into group-aligned blocks once (:meth:`Table.group_by` +
      ``aligned_blocks``; each block holds rows of exactly one group);
      every round gather-compacts the blocks of still-ACTIVE groups and
      folds only those through the task's real block transition, segment-
      merging each block state into its group's accumulator.  Per-round
      cost is O(active rows), so the tail of a skewed-convergence fit
      tracks the groups still iterating instead of G full-table scans.
      Requires the task's default single-scan ``iteration`` and leaf-wise
      merge combinators.
    * ``layout="masked"`` — the fallback (multi-statement ``iteration``
      overrides, generic-merge aggregates): every round vmaps the task's
      pass over per-group validity masks against the full table (O(G·n)).

    ``layout="auto"`` picks segment whenever the task supports it.
    Converged groups are frozen under both layouts.  Returns a
    :class:`FitResult` whose ``state``/``result``/``trace`` carry a
    leading group axis, whose ``n_iters``/``converged`` are per-group
    vectors, and whose ``stats`` records the layout plus (segment) the
    per-round active-row counts and total blocks scanned.  ``warm_start``,
    when given, must already be stacked per group.

    ``mesh`` (defaulting to the table's) runs the WHOLE frozen-group
    driver loop inside one ``shard_map`` program on the segment layout:
    the group-aligned blocks are chunked across the mesh's row axes, each
    round every segment gather-compacts and folds its LOCAL still-active
    blocks, per-group partial states merge with the aggregate's leaf
    combinator collectives, and the replicated driver update / freezing /
    active-row trace proceed exactly as locally — zero host round-trips
    across the fit.  The masked layout ignores ``mesh`` and executes as
    one jit program over the (possibly distributed) rows.
    """
    cols = dict(table.columns)
    gids = cols.pop(key_col).astype(jnp.int32)
    if num_groups is None:
        num_groups = int(jax.device_get(jnp.max(gids))) + 1
    G = num_groups
    if mesh is None:
        mesh = table.mesh
    row_axes = tuple(row_axes or table.row_axes or ("data",))

    if warm_start is not None:
        states0 = jax.tree.map(jnp.asarray, warm_start)
    else:
        s0 = jax.tree.map(jnp.asarray, task.init_state(cols))
        states0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), s0)

    if layout == "auto":
        layout = "segment" if _segment_task_ok(task, states0, cols) \
            else "masked"
    _record("fit", engine=f"grouped-{layout}", sharded=mesh is not None,
            groups=G)
    if layout == "segment":
        return _fit_grouped_segment(task, table, key_col, G, states0,
                                    max_iters, tol, block_size, mask, jit,
                                    mesh=mesh, row_axes=row_axes)
    if layout != "masked":
        raise ValueError(f"unknown layout {layout!r} "
                         "(use 'auto', 'segment' or 'masked')")
    return _fit_grouped_masked(task, cols, gids, G, states0, max_iters,
                               tol, block_size, mask, jit)


def _segment_task_ok(task: IterativeTask, states0, cols) -> bool:
    """Segment layout needs the default single-scan iteration (multi-
    statement rounds drive the pass runner themselves) and an aggregate
    with leaf-wise merge combinators."""
    if type(task).iteration is not IterativeTask.iteration:
        return False
    try:
        agg = task.make_aggregate(jax.tree.map(lambda x: x[0], states0))
        return probe_segment_ops(agg, cols) is not None
    except Exception:
        return False


def _fit_grouped_masked(task, cols, gids, G, states0, max_iters, tol,
                        block_size, mask, jit_):
    """Masked-vmap fallback: every group folds the full table per round."""
    base_mask = mask if mask is not None \
        else jnp.ones((next(iter(cols.values())).shape[0],), jnp.bool_)
    eff_tol = jnp.float32(jnp.inf if tol is None else tol)

    def go(cols, gids, base_mask, states0):
        groups = jnp.arange(G)

        def per_group(g, s):
            runner = PassRunner(cols, (gids == g) & base_mask, block_size)
            new, aux, m = task.iteration(s, runner)
            rec = task.trace_record(new, aux, m)
            return new, aux, jnp.asarray(m, jnp.float32), rec

        vfn = jax.vmap(per_group, in_axes=(0, 0))
        state_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), states0)
        _, aux_s, _, rec_s = jax.eval_shape(vfn, groups, states0)
        trace0 = jax.tree.map(
            lambda s: jnp.zeros((s.shape[0], max_iters) + s.shape[1:],
                                s.dtype), rec_s)

        def cond(c):
            _, _, i, m_vec, _, _ = c
            return jnp.logical_and(i < max_iters, jnp.any(m_vec >= eff_tol))

        def body(c):
            states, aux, i, m_vec, it_vec, trace = c
            active = m_vec >= eff_tol

            def sel(n_, o_):
                act = active.reshape((G,) + (1,) * (n_.ndim - 1))
                return jnp.where(act, n_, o_)

            new, aux_new, m_new, rec = vfn(groups, states)
            states = jax.tree.map(sel, _cast_like(new, state_s), states)
            aux = jax.tree.map(sel, _cast_like(aux_new, aux_s), aux)
            trace = jax.tree.map(
                lambda t, r: t.at[:, i].set(
                    jnp.where(active.reshape((G,) + (1,) * (r.ndim - 1)),
                              r, t[:, i])),
                trace, _cast_like(rec, rec_s))
            if tol is not None:  # counted mode keeps every group active
                m_vec = jnp.where(active, m_new, m_vec)
            it_vec = it_vec + active.astype(jnp.int32)
            return states, aux, i + 1, m_vec, it_vec, trace

        init = (states0, _zeros_of(aux_s), jnp.int32(0),
                jnp.full((G,), jnp.inf, jnp.float32),
                jnp.zeros((G,), jnp.int32), trace0)
        states, aux, _, m_vec, it_vec, trace = jax.lax.while_loop(
            cond, body, init)
        results = jax.vmap(task.finalize)(states, aux)
        return states, results, m_vec, it_vec, trace

    fn = jax.jit(go) if jit_ else go
    states, results, m_vec, it_vec, trace = fn(cols, gids, base_mask, states0)
    n_iters = np.asarray(it_vec)
    converged = np.zeros((G,), bool) if tol is None \
        else np.asarray(m_vec) < tol
    # per-group traces, truncated to the longest-running group
    n_max = int(n_iters.max()) if G else 0
    trace = jax.tree.map(lambda t: np.asarray(t[:, :n_max]), trace)
    return FitResult(states, results, n_iters, converged, trace,
                     {"layout": "masked"})


def _fit_grouped_segment(task, table, key_col, G, states0, max_iters, tol,
                         block_size, mask, jit_, mesh=None, row_axes=()):
    """Partitioned layout: one segment scan over the gather-compacted
    blocks of still-active groups per round.  With ``mesh`` the same loop
    runs inside ONE ``shard_map`` program: every segment owns a chunk of
    whole blocks, compacts/folds its local active ones, and the per-group
    partials merge with the leaf combinator collectives before the
    (replicated) driver update."""
    if type(task).iteration is not IterativeTask.iteration:
        raise ValueError("fit_grouped: layout='segment' requires the "
                         "default single-scan iteration(); multi-statement "
                         "tasks need layout='masked'")
    view = table.group_by(key_col, G)
    n = view.n_rows

    agg0 = task.make_aggregate(jax.tree.map(lambda x: x[0], states0))
    ops = probe_segment_ops(agg0, dict(view.table.columns))
    if ops is None:
        raise ValueError("fit_grouped: layout='segment' needs leaf-wise "
                         "merge combinators; use layout='masked'")

    # Group-aligned blocked layout, built once: each block holds rows of
    # exactly one group, so a round gather-compacts whole blocks.
    pmask = None if mask is None else view.permute(mask)
    bs = segment_block_size(n, G, block_size)
    if mesh is not None:
        row_axes = tuple(row_axes)
        cols, valid, bgids = view.sharded_blocks(mesh, row_axes, bs, pmask)
    else:
        row_axes = ()
        cols, valid, bgids = view.aligned_blocks(bs, pmask)
    # real global block count for stats: sentinel padding blocks (gid G,
    # added only to divide the segment count) are not scannable work
    NB = int(jax.device_get(jnp.sum(bgids < G)))
    counts = view.counts
    eff_tol = jnp.float32(jnp.inf if tol is None else tol)

    def go(cols, valid, bgids, counts, states0):
        nbl = bgids.shape[0]  # engine-local block count (= NB locally)

        def round_core(states, active):
            """One driver round over the compacted local blocks of active
            groups."""
            # sentinel gid G marks sharding-padding blocks: the appended
            # False keeps them out of every round's compaction
            act_ext = jnp.concatenate(
                [active, jnp.zeros((1,), active.dtype)])
            act_blk = act_ext[bgids] if nbl else jnp.zeros((0,), jnp.bool_)
            nb = jnp.sum(act_blk.astype(jnp.int32))
            m_rows = jnp.sum(counts * active.astype(jnp.int32))
            # gather-compact: indices of active blocks, packed to the front
            pos = jnp.cumsum(act_blk.astype(jnp.int32)) - 1
            blk_idx = jnp.zeros((max(nbl, 1),), jnp.int32).at[
                jnp.where(act_blk, pos, nbl)
            ].set(jnp.arange(nbl, dtype=jnp.int32), mode="drop")

            inits = jax.vmap(
                lambda s: task.make_aggregate(s).init(cols))(states)

            def blk_body(carry):
                b, acc = carry
                j = blk_idx[b]
                blk, bm = _block_at(cols, valid, j, bs)
                g = bgids[j]
                acc = segment_block_update(task.make_aggregate, states,
                                           ops, blk, bm, g, acc)
                return b + 1, acc

            _, merged = jax.lax.while_loop(
                lambda c: c[0] < nb, blk_body, (jnp.int32(0), inits))
            if row_axes:
                # second-phase aggregation: per-group partials -> global
                merged = jax.tree.map(
                    partial(_collective_leaf, axes=row_axes), ops, merged)

            def g_post(s, agg_state):
                a = task.make_aggregate(s)
                out = a.final(agg_state)
                new = task.update(s, out)
                mm = task.metric(s, new, out)
                return new, out, jnp.asarray(mm, jnp.float32), \
                    task.trace_record(new, out, mm)

            new, aux, m_new, rec = jax.vmap(g_post)(states, merged)
            return new, aux, m_new, rec, m_rows, nb

        state_s = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), states0)
        _, aux_s, _, rec_s, *_ = jax.eval_shape(
            round_core, states0, jnp.ones((G,), jnp.bool_))
        trace0 = jax.tree.map(
            lambda s: jnp.zeros((s.shape[0], max_iters) + s.shape[1:],
                                s.dtype), rec_s)

        def cond(c):
            i, m_vec = c[2], c[3]
            return jnp.logical_and(i < max_iters, jnp.any(m_vec >= eff_tol))

        def body(c):
            states, aux, i, m_vec, it_vec, trace, blk_tot, act_tr = c
            active = m_vec >= eff_tol
            new, aux_new, m_new, rec, m_rows, nb = round_core(states, active)

            def sel(n_, o_):
                act = active.reshape((G,) + (1,) * (n_.ndim - 1))
                return jnp.where(act, n_, o_)

            states = jax.tree.map(sel, _cast_like(new, state_s), states)
            aux = jax.tree.map(sel, _cast_like(aux_new, aux_s), aux)
            trace = jax.tree.map(
                lambda t, r: t.at[:, i].set(
                    jnp.where(active.reshape((G,) + (1,) * (r.ndim - 1)),
                              r, t[:, i])),
                trace, _cast_like(rec, rec_s))
            if tol is not None:  # counted mode keeps every group active
                m_vec = jnp.where(active, m_new, m_vec)
            it_vec = it_vec + active.astype(jnp.int32)
            return (states, aux, i + 1, m_vec, it_vec, trace,
                    blk_tot + nb, act_tr.at[i].set(m_rows))

        init = (states0, _zeros_of(aux_s), jnp.int32(0),
                jnp.full((G,), jnp.inf, jnp.float32),
                jnp.zeros((G,), jnp.int32), trace0, jnp.int32(0),
                jnp.zeros((max_iters,), jnp.int32))
        states, aux, n_rounds, m_vec, it_vec, trace, blk_tot, act_tr = \
            jax.lax.while_loop(cond, body, init)
        if row_axes:  # total blocks actually folded, across all segments
            blk_tot = jax.lax.psum(blk_tot, row_axes)
        results = jax.vmap(task.finalize)(states, aux)
        return (states, results, m_vec, it_vec, trace, n_rounds, blk_tot,
                act_tr)

    if mesh is not None:
        col_spec = jax.tree.map(
            lambda v: row_pspec(row_axes, v.ndim), cols)
        go = jax.shard_map(
            go, mesh=mesh,
            in_specs=(col_spec, row_pspec(row_axes), row_pspec(row_axes),
                      P(), P()),
            out_specs=P(), check_vma=False)
    fn = jax.jit(go) if jit_ else go
    (states, results, m_vec, it_vec, trace, n_rounds, blk_tot, act_tr) = fn(
        cols, valid, bgids, counts, states0)
    n_iters = np.asarray(it_vec)
    converged = np.zeros((G,), bool) if tol is None \
        else np.asarray(m_vec) < tol
    # per-group traces, truncated to the longest-running group
    n_max = int(n_iters.max()) if G else 0
    trace = jax.tree.map(lambda t: np.asarray(t[:, :n_max]), trace)
    n_rounds = int(n_rounds)
    stats = {
        "layout": "segment",
        "sharded": mesh is not None,
        "block_size": bs,
        "rounds": n_rounds,
        "blocks": int(blk_tot),
        "blocks_full_scan": n_rounds * NB,
        "active_rows": np.asarray(act_tr)[:n_rounds],
    }
    return FitResult(states, results, n_iters, converged, trace, stats)
