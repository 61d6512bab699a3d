"""Sharded Table abstraction — MADlib's distributed-by-hash table in JAX.

A :class:`Table` is the macro-programming unit of MADJAX: a pytree of
equal-length *columns* (arrays whose leading axis is the row axis), plus the
sharding metadata that says how rows are distributed across the mesh.  It is
the analogue of a Greenplum table ``DISTRIBUTED BY``: rows are partitioned
over the batch-like mesh axes ("segments"), and every aggregate/driver in
:mod:`repro.core` consumes tables.

Unlike an RDBMS table, columns may be multi-dimensional (a ``DOUBLE
PRECISION[]`` column is simply a ``(n_rows, d)`` array — the paper stores
feature vectors exactly this way in §4.1).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Iterator, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

Columns = Mapping[str, jax.Array]


def _n_rows(columns: Columns) -> int:
    sizes = {k: v.shape[0] for k, v in columns.items()}
    if len(set(sizes.values())) != 1:
        raise ValueError(f"ragged table: column row counts differ: {sizes}")
    return next(iter(sizes.values()))


@jax.jit
def take_rows(v: jax.Array, idx: jax.Array) -> jax.Array:
    """``v[idx]`` along the row axis, one feature column at a time.

    A TPU lays an ``(n, k)`` f32 column out rows-on-lanes when ``k`` is
    not a multiple of 128, and a whole-row gather then relayouts it to
    rows-on-sublanes and back: about ``2 * n * 128 * 4`` bytes of scratch
    (10 GB at 10M rows), whatever ``k``.  ``k`` one-dimensional gathers
    read and write the rows-on-lanes layout as it is.
    """
    if v.ndim == 1:
        return v[idx]
    flat = v.reshape(v.shape[0], -1)
    out = jax.lax.map(lambda col: col[idx], flat.T)
    return out.T.reshape(idx.shape + v.shape[1:])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Table:
    """A pytree of named columns sharing a leading row axis.

    ``columns`` maps column name -> array of shape ``(n_rows, ...)``.
    ``mesh`` / ``row_axes`` record how rows are distributed (may be None for
    a host-local table).
    """

    columns: dict[str, jax.Array]
    mesh: Mesh | None = None
    row_axes: tuple[str, ...] = ()
    # group_by memo: (key_col, num_groups) -> (version, GroupedView).
    # Host-side state private to this instance — never flattened into the
    # pytree, compared or hashed; derived tables (select/with_column/...)
    # start empty.  Entries are stamped with the table version they were
    # built at, so every lookup observes staleness (see group_by).
    _gb_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # sort memo: key_col -> (version, (sorted_keys, perm)).  One level
    # below the group_by memo: the raw stable argsort of a column, shared
    # by GROUP BY partitioning AND sort-merge join key resolution
    # (core/join.py) — one argsort per (table, key), whoever asks first.
    # Same host-side / version-stamp discipline as _gb_cache.
    _sort_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # Versioning (the IVM contract): ``_version`` bumps on EVERY mutation
    # (append or invalidate); ``_epoch`` bumps only on non-append
    # mutations (invalidate).  A retained fold state pinned at
    # (version v, epoch e, n_rows r) may be brought current by folding
    # ONLY rows [r:] iff the table's epoch is still e — the row prefix is
    # then guaranteed unchanged.  Host-side, never part of the pytree.
    _version: int = dataclasses.field(default=0, repr=False, compare=False)
    _epoch: int = dataclasses.field(default=0, repr=False, compare=False)
    # Mutation hooks (the eviction contract's push side): callables
    # ``hook(table)`` invoked host-side after every version bump, so
    # external caches keyed on this table (the analytics server's result
    # cache) evict eagerly instead of waiting to observe a version
    # mismatch.  Host-side state, never part of the pytree; derived
    # tables start with no hooks.
    _mutation_hooks: list = dataclasses.field(
        default_factory=list, repr=False, compare=False)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        return tuple(self.columns[n] for n in names), (names, self.mesh, self.row_axes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, mesh, row_axes = aux
        return cls(dict(zip(names, children)), mesh, row_axes)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_columns(cls, columns: Columns) -> "Table":
        cols = {k: jnp.asarray(v) for k, v in columns.items()}
        _n_rows(cols)
        return cls(cols)

    def distribute(self, mesh: Mesh, row_axes: Sequence[str] = ("data",)) -> "Table":
        """Shard rows over ``row_axes`` of ``mesh`` (Greenplum DISTRIBUTED BY).

        Rows must divide the product of the named axis sizes; callers pad via
        :meth:`pad_to` first when needed.
        """
        from ..distributed.sharding import distribute_rows
        row_axes = tuple(row_axes)
        segs = int(np.prod([mesh.shape[a] for a in row_axes]))
        n = self.n_rows
        if n % segs:
            raise ValueError(f"n_rows={n} not divisible by {segs} segments; pad first")
        return Table(distribute_rows(mesh, row_axes, dict(self.columns)),
                     mesh, row_axes)

    # -- basic relational ops ----------------------------------------------
    @property
    def n_rows(self) -> int:
        return _n_rows(self.columns)

    @property
    def column_names(self) -> tuple[str, ...]:
        return tuple(sorted(self.columns))

    def __getitem__(self, name: str) -> jax.Array:
        return self.columns[name]

    def select(self, *names: str) -> "Table":
        return Table({n: self.columns[n] for n in names}, self.mesh, self.row_axes)

    def _place_rows(self, columns: dict) -> dict:
        """Re-place columns to this table's row sharding (no-op host-local).

        Every method that returns a Table carrying this table's
        ``mesh`` / ``row_axes`` MUST route fresh columns through here —
        otherwise the result lies about its layout to the sharded
        engines (new arrays would stay ``SingleDeviceSharding``).
        """
        if self.mesh is None or not columns:
            return columns
        from ..distributed.sharding import distribute_rows
        segs = int(np.prod([self.mesh.shape[a] for a in self.row_axes]))
        n = _n_rows(columns)
        if n % segs:
            raise ValueError(
                f"n_rows={n} not divisible by {segs} segments of the "
                f"table's mesh; pad before distributing")
        return distribute_rows(self.mesh, self.row_axes, columns)

    def with_column(self, name: str, values: jax.Array) -> "Table":
        cols = dict(self.columns)
        cols[name] = jnp.asarray(values)
        _n_rows(cols)
        if self.mesh is not None:
            from ..distributed.sharding import row_sharding
            cols[name] = jax.device_put(
                cols[name],
                row_sharding(self.mesh, self.row_axes, cols[name].ndim))
        return Table(cols, self.mesh, self.row_axes)

    def map_rows(self, fn: Callable[[Columns], Columns]) -> "Table":
        """Row-wise projection (a SELECT of expressions); traced & fused by XLA."""
        return Table(self._place_rows(dict(fn(self.columns))),
                     self.mesh, self.row_axes)

    def pad_to(self, n: int, fill: float = 0.0) -> tuple["Table", jax.Array]:
        """Pad to ``n`` rows; returns (padded table with a __valid__ mask column)."""
        cur = self.n_rows
        if n < cur:
            raise ValueError(f"pad_to({n}) smaller than n_rows={cur}")
        cols = {}
        for k, v in self.columns.items():
            pad = [(0, n - cur)] + [(0, 0)] * (v.ndim - 1)
            cols[k] = jnp.pad(v, pad, constant_values=fill)
        mask = jnp.arange(n) < cur
        if self.mesh is not None:
            from ..distributed.sharding import row_sharding
            cols = self._place_rows(cols)
            mask = jax.device_put(
                mask, row_sharding(self.mesh, self.row_axes, mask.ndim))
        return Table(cols, self.mesh, self.row_axes), mask

    def blocks(self, block_size: int) -> Iterator["Table"]:
        """Host-side iterator of row blocks (the out-of-core / streaming path)."""
        n = self.n_rows
        for start in range(0, n, block_size):
            stop = min(start + block_size, n)
            yield Table(
                {k: v[start:stop] for k, v in self.columns.items()},
                self.mesh,
                self.row_axes,
            )

    def row_spec(self) -> "Table":
        """ShapeDtypeStruct skeleton of this table (for lowering without data)."""
        cols = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in self.columns.items()
        }
        return Table(cols, self.mesh, self.row_axes)

    def group_by(self, key_col: str, num_groups: int | None = None
                 ) -> "GroupedView":
        """Partition rows by an integer group-id column (sort once, scan many).

        Returns a :class:`GroupedView`: the data columns permuted so each
        group's rows form one contiguous segment, plus the segment
        boundaries.  This is Greenplum's "redistribute by grouping key"
        materialized once up front — every grouped engine
        (``run_grouped`` / ``fit_grouped``) then folds the partitioned
        layout in O(n) instead of re-masking the full table per group.

        The view is **memoized** per ``(key_col, num_groups)`` on this
        Table instance, so every grouped statement and every
        ``fit_grouped`` over the same key shares ONE partitioning sort —
        the plan layer's sort dedup rests on this cache.  A ``None``
        group count also caches under its resolved value.  Entries are
        stamped with the table :attr:`version`, so :meth:`append` and
        :meth:`invalidate` retire them automatically — a hit is served
        only when the stamp matches the current version.  Derived
        tables (``select`` / ``with_column`` / ...) are new instances
        with empty caches; mutating ``columns`` in place requires an
        explicit :meth:`invalidate`.

        Out-of-range ids (``< 0`` or ``>= num_groups``) keep their rows in
        the permuted table but outside every segment; grouped engines
        ignore them, matching the masked semantics of ``gid == g``.
        """
        view = self.cached_group_by(key_col, num_groups)
        if view is not None:
            return view
        view = self._group_by_uncached(key_col, num_groups)
        self._gb_cache[(key_col, num_groups)] = (self._version, view)
        self._gb_cache[(key_col, view.num_groups)] = (self._version, view)
        return view

    def cached_group_by(self, key_col: str, num_groups: int | None = None
                        ) -> "GroupedView | None":
        """Version-checked :meth:`group_by` memo lookup: the memoized view
        for ``(key_col, num_groups)`` if one exists AND was built at the
        table's current :attr:`version`, else ``None``.  Never sorts.

        This is the ONLY sanctioned way for code outside this class (the
        plan layer's cost model, method wrappers) to peek at the memo —
        a direct ``_gb_cache`` read would resurrect views that an
        :meth:`append` or :meth:`invalidate` has already outdated.
        """
        hit = self._gb_cache.get((key_col, num_groups))
        if hit is None or hit[0] != self._version:
            return None
        return hit[1]

    @property
    def version(self) -> int:
        """Monotonic mutation counter.  Bumped by :meth:`append` and
        :meth:`invalidate`; anything caching state derived from this
        table's rows (group_by views, retained fold states, prepared
        programs keyed on table identity) must stamp the version it read
        and treat a mismatch as stale."""
        return self._version

    @property
    def epoch(self) -> int:
        """Append-survivor counter.  Bumped only by :meth:`invalidate`
        (arbitrary mutation); NOT by :meth:`append`.  While the epoch is
        unchanged, the row prefix ``[0:r]`` observed at any earlier
        version is guaranteed intact, so retained fold states may be
        brought current by folding only the appended suffix (the
        incremental-view-maintenance contract)."""
        return self._epoch

    def append(self, columns: Columns) -> "Table":
        """Append rows in place (the append-only ingest path) and bump
        :attr:`version`.

        ``columns`` must carry exactly this table's columns with matching
        dtypes and trailing shapes.  Existing rows are untouched —
        :attr:`epoch` does NOT bump — so retained statements
        (:func:`repro.core.materialize`) refresh by delta-folding only
        the new rows and merging with the aggregates' own combinators.
        Memoized :meth:`group_by` views are invalidated automatically via
        the version stamp (a later ``group_by`` re-sorts).

        On a distributed table the concatenated columns are re-placed
        over the mesh; the new row count must still divide the segment
        count.  Returns ``self`` for chaining.
        """
        new = {k: jnp.asarray(v) for k, v in columns.items()}
        if set(new) != set(self.columns):
            raise ValueError(
                f"append columns {sorted(new)} != table columns "
                f"{sorted(self.columns)}")
        _n_rows(new)
        cols = {}
        for k, old in self.columns.items():
            v = new[k]
            if v.dtype != old.dtype:
                raise ValueError(
                    f"append column {k!r}: dtype {v.dtype} != {old.dtype}")
            if v.shape[1:] != old.shape[1:]:
                raise ValueError(
                    f"append column {k!r}: trailing shape {v.shape[1:]} "
                    f"!= {old.shape[1:]}")
            cols[k] = jnp.concatenate([old, v], axis=0)
        cols = self._place_rows(cols)
        self.columns.clear()
        self.columns.update(cols)
        self._version += 1
        self._notify_mutation()
        return self

    def invalidate(self) -> None:
        """Declare arbitrary in-place mutation: drops every memoized
        :meth:`group_by` view and bumps BOTH :attr:`version` and
        :attr:`epoch`, so every downstream cache — gb memo, retained
        materialized states, plan-time cost lookups — observes staleness
        instead of relying on caller discipline.  Functional derivations
        (``select`` / ``with_column`` / ...) never need this; they return
        fresh instances.  Use :meth:`append` for append-only growth — it
        keeps the epoch so incremental refresh stays possible."""
        self._gb_cache.clear()
        self._sort_cache.clear()
        self._version += 1
        self._epoch += 1
        self._notify_mutation()

    def on_mutation(self, hook: Callable[["Table"], None]) -> None:
        """Register ``hook(table)`` to run after every mutation that bumps
        :attr:`version` (:meth:`append` and :meth:`invalidate`) — the
        push-side of the staleness contract.  External version-keyed
        caches (the analytics server's result cache) use this to evict
        entries for this table the moment it moves, rather than holding
        dead state until a probe notices the version mismatch.  Hooks run
        host-side, synchronously, in registration order; deregister with
        :meth:`remove_mutation_hook`."""
        self._mutation_hooks.append(hook)

    def remove_mutation_hook(self, hook: Callable[["Table"], None]) -> None:
        """Deregister a :meth:`on_mutation` hook (no-op if absent)."""
        try:
            self._mutation_hooks.remove(hook)
        except ValueError:
            pass

    def _notify_mutation(self) -> None:
        for hook in list(self._mutation_hooks):
            hook(self)

    def sort_permutation(self, key_col: str
                         ) -> tuple[jax.Array, jax.Array]:
        """Memoized stable argsort of one column: ``(sorted_keys, perm)``
        with ``sorted_keys == self[key_col][perm]``.

        This is THE partitioning sort of the engine — hoisted out of
        :meth:`group_by` so GROUP BY partitioning and sort-merge join key
        resolution (:mod:`repro.core.join`) share one argsort per
        ``(table, key)``: a dimension table grouped by its key and joined
        on the same key pays the sort once, whichever path asks first.
        Memoized per ``key_col`` with the same version-stamp staleness
        contract as the :meth:`group_by` memo; a miss records ONE
        ``kind="sort"`` trace event tagged ``table=id(self)`` (the
        per-table rollup in :meth:`Trace.summary` counts these), a hit
        records nothing.
        """
        hit = self._sort_cache.get(key_col)
        if hit is not None and hit[0] == self._version:
            return hit[1]
        from .trace import record
        record("sort", key_col=key_col, n_rows=self.n_rows,
               table=id(self))
        keys = self.columns[key_col]
        perm = jnp.argsort(keys, stable=True)
        out = (keys[perm], perm)
        self._sort_cache[key_col] = (self._version, out)
        return out

    def _group_by_uncached(self, key_col: str, num_groups: int | None
                           ) -> "GroupedView":
        sorted_keys, perm = self.sort_permutation(key_col)
        sorted_gids = sorted_keys.astype(jnp.int32)
        if num_groups is None:
            num_groups = int(jax.device_get(jnp.max(sorted_gids))) + 1
        offsets = jnp.searchsorted(
            sorted_gids, jnp.arange(num_groups + 1, dtype=jnp.int32)
        ).astype(jnp.int32)
        # the partitioned copy keeps the table's row sharding: the gather
        # leaves it replicated, a whole table on every chip
        data = self._place_rows({k: take_rows(v, perm)
                                 for k, v in self.columns.items()
                                 if k != key_col})
        return GroupedView(
            Table(data, self.mesh, self.row_axes), sorted_gids, perm,
            num_groups, jnp.diff(offsets), offsets,
        )


@dataclasses.dataclass
class GroupedView:
    """Partitioned ``GROUP BY`` layout of a :class:`Table`.

    ``table`` holds the data columns (group-id column stripped) with rows
    permuted so group ``g`` occupies the contiguous segment
    ``offsets[g]:offsets[g + 1]``; ``gids`` is the sorted id column,
    ``perm`` maps partitioned position -> original row, and ``counts``
    is rows per group.  Built by :meth:`Table.group_by`; the sort is paid
    once and shared by every subsequent grouped scan.
    """

    table: Table
    gids: jax.Array            # (n,) int32, sorted ascending
    perm: jax.Array            # (n,) int32, partitioned position -> orig row
    num_groups: int
    counts: jax.Array          # (G,) rows per group
    offsets: jax.Array         # (G + 1,) segment boundaries

    @property
    def n_rows(self) -> int:
        return self.table.n_rows

    def select(self, *names: str) -> "GroupedView":
        """Subset of data columns sharing this view's partitioning (the
        sort is NOT re-paid)."""
        return GroupedView(self.table.select(*names), self.gids, self.perm,
                           self.num_groups, self.counts, self.offsets)

    def permute(self, rows: jax.Array) -> jax.Array:
        """Bring a row-aligned array (e.g. a base mask) into the
        partitioned row order."""
        return jnp.asarray(rows)[self.perm]

    def aligned_blocks(self, block_size: int,
                       base_mask: jax.Array | None = None, *,
                       pad_blocks_to: int | None = None):
        """Group-aligned blocked layout: every group's segment zero-padded
        to a whole number of ``block_size`` row blocks, so each block holds
        rows of exactly ONE group.

        Returns ``(columns, valid, block_gids)``: columns with leading axis
        ``n_blocks * block_size``, a validity mask over real (and
        base-mask-passing) rows, and the single group id of each block.
        Empty groups get no blocks; out-of-range ids fall outside every
        segment and are dropped.  ``base_mask`` must already be in
        partitioned order (see :meth:`permute`).  Padding overhead is
        bounded by ``num_groups * block_size`` rows, so callers pick
        ``block_size`` near the typical segment size.

        ``pad_blocks_to`` rounds the block count up to a multiple (the
        sharded engine needs blocks to divide evenly across segments);
        padding blocks carry the sentinel group id ``num_groups`` (out of
        range: scatters drop them, active-group compaction never selects
        them) with every row masked invalid.
        """
        bs = int(block_size)
        counts = np.asarray(jax.device_get(self.counts))
        starts = np.asarray(jax.device_get(self.offsets))[:-1]
        bpg = -(-counts // bs)  # blocks per group (0 for empty groups)
        bg_np = np.repeat(np.arange(self.num_groups), bpg).astype(np.int32)
        ppg = bpg * bs          # padded rows per group
        n2 = int(ppg.sum())
        if n2 == 0:
            # No real blocks (all groups empty / every id out of range).
            # Still honour pad_blocks_to: emit that many sentinel blocks
            # so sharded layouts keep their every-segment-owns-whole-
            # blocks contract even for an empty view.  Sentinel columns
            # are constructed, not gathered — the table may have 0 rows.
            pad = int(pad_blocks_to) if pad_blocks_to else 0
            cols = {
                k: jnp.zeros((pad * bs,) + v.shape[1:], v.dtype)
                for k, v in self.table.columns.items()
            }
            return (cols, jnp.zeros((pad * bs,), jnp.bool_),
                    jnp.full((pad,), self.num_groups, jnp.int32))
        grp = np.repeat(np.arange(self.num_groups), ppg)
        out_start = np.concatenate([[0], np.cumsum(ppg)])[:-1]
        local = np.arange(n2) - out_start[grp]
        valid_np = local < counts[grp]
        src_np = np.where(valid_np, starts[grp] + local, 0).astype(np.int32)
        if pad_blocks_to:
            extra = -len(bg_np) % int(pad_blocks_to)
            if extra:
                bg_np = np.concatenate(
                    [bg_np,
                     np.full(extra, self.num_groups, np.int32)])
                src_np = np.concatenate(
                    [src_np, np.zeros(extra * bs, np.int32)])
                valid_np = np.concatenate(
                    [valid_np, np.zeros(extra * bs, bool)])
        src = jnp.asarray(src_np)
        cols = {k: take_rows(v, src) for k, v in self.table.columns.items()}
        valid = jnp.asarray(valid_np)
        if base_mask is not None:
            valid = valid & jnp.asarray(base_mask)[src]
        return cols, valid, jnp.asarray(bg_np)

    def sharded_blocks(self, mesh: Mesh, row_axes=("data",),
                       block_size: int = 4096,
                       base_mask: jax.Array | None = None):
        """:meth:`aligned_blocks` distributed across the mesh's row axes.

        The block count is padded to a multiple of the segment count and
        the rows / validity mask / block-gid vector are placed with
        contiguous whole-block chunks per device, so each segment owns an
        integral run of group-aligned blocks — the MADlib two-phase
        layout: every segment folds its local blocks, per-group partial
        states merge across segments with the aggregate's combinators.
        """
        from ..distributed.sharding import distribute_rows, row_sharding
        row_axes = tuple(row_axes)
        segs = int(np.prod([mesh.shape[a] for a in row_axes]))
        cols, valid, bgids = self.aligned_blocks(
            block_size, base_mask, pad_blocks_to=segs)
        cols = distribute_rows(mesh, row_axes, dict(cols))
        valid = jax.device_put(valid, row_sharding(mesh, row_axes))
        bgids = jax.device_put(bgids, row_sharding(mesh, row_axes))
        return cols, valid, bgids


def synthetic_regression_table(
    key: jax.Array, n_rows: int, n_vars: int, noise: float = 0.1,
    dtype: Any = jnp.float32,
) -> tuple[Table, jax.Array]:
    """The paper's linregr benchmark data: y = <b, x> + eps (§4.4)."""
    kx, kb, ke = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n_rows, n_vars), dtype)
    b = jax.random.normal(kb, (n_vars,), dtype)
    y = x @ b + noise * jax.random.normal(ke, (n_rows,), dtype)
    return Table.from_columns({"x": x, "y": y}), b


def synthetic_classification_table(
    key: jax.Array, n_rows: int, n_vars: int, dtype: Any = jnp.float32
) -> tuple[Table, jax.Array]:
    """Logistic data: Pr[y=1|x] = sigmoid(<b, x>) (§4.2)."""
    kx, kb, ku = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n_rows, n_vars), dtype)
    b = jax.random.normal(kb, (n_vars,), dtype)
    p = jax.nn.sigmoid(x @ b)
    y = (jax.random.uniform(ku, (n_rows,)) < p).astype(dtype)
    return Table.from_columns({"x": x, "y": y}), b
