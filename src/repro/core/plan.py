"""Logical plans over the engine matrix — the declarative layer (§3.2).

MADlib's interface is declarative: the analyst issues *statements* and
the database decides how to execute them, sharing work across the query
where it can.  Feng et al. ("Towards a Unified Architecture for
in-RDBMS Analytics") and sql4ml argue the same split — declarative
statements above, ONE unified execution architecture below.  This module
is the layer above our engine matrix: method wrappers stop calling
``run_local`` / ``run_sharded`` / ``run_grouped`` / ``fit*`` directly
and instead emit **logical plan nodes**; the planner then

* **shares scans across statements** — every compatible :class:`ScanAgg`
  over the same (table, mask, block size) fuses into ONE ``run_many``
  pass, every compatible :class:`GroupedScanAgg` over the same
  (table, group column) into ONE ``run_grouped`` pass, and
  :class:`StreamAgg` statements over the same block source into ONE
  ``run_stream`` fold (mandatory there: a shared iterator can only be
  consumed once).  ``profile``'s PR-1 hand-built fusion now *falls out*
  of this optimizer;
* **dedups sorts** — grouped passes resolve their :class:`GroupedView`
  through the memoized :meth:`Table.group_by`, so N grouped statements
  (and ``fit_grouped``) over one key pay ONE partitioning sort;
* **fuses joined statements** — :class:`JoinedGroupedScanAgg` statements
  over one ``(fact, dim, key, attr)`` star triple share ONE device-side
  sort-merge key resolution (:class:`~repro.core.join.Join`, memoized)
  and ONE segment scan; the cost model prices the sort-share strategy
  against gather-materializing the dimension onto fact rows
  (:func:`join_cost`), and ``explain()`` renders the join and its
  shared sort;
* **selects engines cost-based** — candidates come from
  :data:`ENGINE_CAPS` (the capability matrix) filtered by what the
  statement needs (mask? group_by? fit? stream?), ranked by a row-cost
  model (rows × mesh segments × generic-merge fallbacks), and the
  chosen physical plan renders like ``EXPLAIN`` via
  :meth:`PhysicalPlan.explain`.

Fusion is *refused loudly* when it would be wrong: statements with
different base masks (or tables, or block partitionings) must never fold
into one ``run_many`` — one statement's filter would silently apply to
another.  The planner keys passes so this cannot happen, and the pass
constructors re-check and raise (:func:`fused_scan_pass`).

Correctness contract: fusing changes the number of physical passes and
NOTHING else.  Members run their own transitions on the same blocked
partitioning as a solo run, so exact-state aggregates (integer sketches,
histogram counts, dyadic sums) are **bit-identical** to per-statement
execution; templated members (``ProfileAggregate``) see exactly their
statement's columns through the :class:`_Projected` adapter even when
the fused block carries more.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import calibration as _calibration
from .aggregates import (
    Aggregate, _fused_for, probe_segment_ops, run_grouped, run_many,
    run_stream, segment_block_size,
)
from .iterative import (
    IterativeTask, _segment_task_ok, fit, fit_grouped, fit_stream,
)
from .join import Join
from .table import Columns, GroupedView, Table
from .trace import record as _record, span as _span

# ---------------------------------------------------------------------------
# The capability matrix — which cross-cutting features each engine honors.
# (The docstring table in core/__init__ is rendered from this data; the
# planner filters candidate engines through it before costing them.)
# ---------------------------------------------------------------------------

ENGINE_CAPS: dict[str, dict[str, bool]] = {
    "local":           {"mask": True,  "group_by": False, "fit": True,
                        "stream": False},
    "sharded":         {"mask": True,  "group_by": False, "fit": True,
                        "stream": False},
    "stream":          {"mask": False, "group_by": False, "fit": True,
                        "stream": True},
    "grouped-segment": {"mask": True,  "group_by": True,  "fit": True,
                        "stream": False},
    "grouped-masked":  {"mask": True,  "group_by": True,  "fit": True,
                        "stream": False},
    "sharded-grouped": {"mask": True,  "group_by": True,  "fit": True,
                        "stream": False},
}


# ---------------------------------------------------------------------------
# Logical plan nodes.
# ---------------------------------------------------------------------------

# ``columns`` on scan nodes is the statement's projection: a tuple of
# column names, or a {target: source} mapping when the aggregate expects
# renamed keys (``linregr`` reading x from "features").  None = the whole
# table.  Projections are semantic, not just an optimization: templated
# aggregates (ProfileAggregate) profile exactly the columns they see.
Projection = "tuple[str, ...] | Mapping[str, str] | None"


@dataclasses.dataclass(eq=False)
class ScanAgg:
    """One-pass aggregate over a table (``SELECT agg(...) FROM t``)."""

    agg: Aggregate
    table: Table
    columns: Any = None          # Projection
    mask: Any = None             # base row filter, table row order
    block_size: int | None = None
    engine: str = "auto"         # "auto" | "local" | "sharded"
    jit: bool = True
    label: str | None = None


@dataclasses.dataclass(eq=False)
class GroupedScanAgg:
    """Grouped aggregate (``SELECT g, agg(...) FROM t GROUP BY g``).

    ``table`` may be a prebuilt :class:`GroupedView` (``group_col``
    ignored); otherwise the planner resolves the view through the
    memoized ``Table.group_by`` — the sort-dedup point.
    """

    agg: Aggregate
    table: Any                   # Table | GroupedView
    group_col: str | None = None
    num_groups: int | None = None
    columns: Any = None          # Projection (of the view's data columns)
    mask: Any = None
    block_size: int | None = None
    method: str = "auto"         # "auto" | "segment" | "masked"
    mesh: Any = None             # None -> the table's mesh (may be None)
    row_axes: Any = None
    jit: bool = True
    label: str | None = None


@dataclasses.dataclass(eq=False)
class JoinedGroupedScanAgg:
    """Grouped aggregate over an equi-join (``SELECT dim.attr, agg(...)
    FROM fact JOIN dim GROUP BY dim.attr``) — the first multi-table
    statement.

    ``join`` is a :class:`~repro.core.join.Join` spec; the planner
    resolves it via the memoized device-side sort-merge (one dimension
    key argsort + one searchsorted, producing a fact-aligned group-id
    column) and routes the result through the ordinary grouped core —
    the dimension's columns are never materialized onto fact rows.
    Statements over one (fact, dim, key, attr) triple fuse into ONE
    pass; ``num_groups`` defaults to ``max(dim.attr) + 1``.  ``mask``
    (like ``columns``) is in FACT row order — the joined table is
    fact-row-aligned.
    """

    agg: Aggregate
    join: Join
    num_groups: int | None = None
    columns: Any = None          # Projection (of the fact's columns)
    mask: Any = None             # base row filter, fact row order
    block_size: int | None = None
    method: str = "auto"         # "auto" | "segment" | "masked"
    mesh: Any = None             # None -> the fact table's mesh
    row_axes: Any = None
    jit: bool = True
    label: str | None = None


@dataclasses.dataclass(eq=False)
class IterativeFit:
    """Iterative model fit (the §3.1.2 driver pattern as a statement).

    Dispatches on its attributes: ``blocks`` set -> ``fit_stream``;
    ``group_col`` set -> ``fit_grouped``; else ``fit``.  Fit statements
    never fuse with one another — each owns its driver loop — but they
    share partitioning sorts with grouped scans through the same
    ``group_by`` memo.
    """

    task: IterativeTask
    table: Table | None = None
    blocks: Callable[[], Iterable[Columns]] | None = None
    group_col: str | None = None
    num_groups: int | None = None
    max_iters: int = 100
    tol: float | None = 1e-6
    engine: str = "auto"         # fit(): "auto" | "local" | "sharded"
    mode: str = "compiled"
    layout: str = "auto"         # fit_grouped(): "auto"|"segment"|"masked"
    block_size: int | None = None
    mask: Any = None
    warm_start: Any = None
    mesh: Any = None
    row_axes: Any = None
    jit: bool = True
    label: str | None = None


@dataclasses.dataclass(eq=False)
class StreamAgg:
    """One-pass aggregate over an out-of-core block stream.

    ``blocks`` is an iterable of column dicts or a zero-arg factory.
    Statements sharing the same ``blocks`` object MUST fuse (the planner
    does): a shared iterator can only be consumed once.
    """

    agg: Aggregate
    blocks: Any
    columns: Any = None          # Projection
    label: str | None = None


Node = ("ScanAgg | GroupedScanAgg | JoinedGroupedScanAgg | IterativeFit"
        " | StreamAgg")


# ---------------------------------------------------------------------------
# Projection adapter — a member sees exactly its statement's columns.
# ---------------------------------------------------------------------------

def _normalize_projection(columns) -> dict[str, str] | None:
    if columns is None:
        return None
    if isinstance(columns, Mapping):
        return dict(columns)
    return {name: name for name in columns}


class _Projected(Aggregate):
    """Feed a fused member only its statement's (possibly renamed)
    columns.  All merge/final behavior delegates to the wrapped
    aggregate, so fusion stays a pure scan-sharing transform."""

    merge_ops = None  # never consulted: every path below delegates

    def __init__(self, agg: Aggregate, columns):
        self.agg = agg
        self.projection = _normalize_projection(columns)

    def _project(self, block):
        if self.projection is None:
            return block
        return {tgt: block[src] for tgt, src in self.projection.items()}

    def init(self, block):
        return self.agg.init(self._project(block))

    def transition(self, state, block, mask):
        return self.agg.transition(state, self._project(block), mask)

    def merge(self, a, b):
        return self.agg.merge(a, b)

    def mesh_merge(self, state, axes):
        return self.agg.mesh_merge(state, axes)

    def segment_ops(self, state):
        return self.agg.segment_ops(state)

    def final(self, state):
        return self.agg.final(state)

    # Kernel hook + calibration class forward to the wrapped aggregate,
    # so projection never hides the grouped fast path or the planner's
    # cost bucket; segment_kernel_args applies this member's projection,
    # so the kernel reads the statement's (possibly renamed) columns.
    @property
    def segment_kernel(self):
        return self.agg.segment_kernel

    @property
    def kernel_impl(self):
        return self.agg.kernel_impl

    @property
    def cost_class(self):
        return self.agg.cost_class

    def segment_kernel_args(self, columns, valid, block_gids, num_groups):
        return self.agg.segment_kernel_args(self._project(columns), valid,
                                            block_gids, num_groups)


# Wrapper memo: planning the same statement again (a bench rep, a
# repeated prepared batch) must yield the SAME projected-aggregate
# object, so run_many's fused cache — and through it the local engine's
# program cache — hits instead of recompiling.  Entries pin their
# wrapped aggregates, so live keys can't collide.  Bounded FIFO.
_PROJECTED_CACHE: dict[tuple, "_Projected"] = {}
_PROJECTED_CACHE_MAX = 512


def _member_agg(node) -> Aggregate:
    columns = getattr(node, "columns", None)
    if columns is None:
        return node.agg
    proj = _normalize_projection(columns)
    key = (id(node.agg), tuple(sorted(proj.items())))
    hit = _PROJECTED_CACHE.get(key)
    if hit is not None and hit.agg is node.agg:
        return hit
    wrapped = _Projected(node.agg, proj)
    if len(_PROJECTED_CACHE) >= _PROJECTED_CACHE_MAX:
        _PROJECTED_CACHE.pop(next(iter(_PROJECTED_CACHE)))
    _PROJECTED_CACHE[key] = wrapped
    return wrapped


# ---------------------------------------------------------------------------
# Cost model — the ranking behind engine selection.  With an ACTIVE
# measured calibration (see repro.core.calibration) candidates rank by
# interpolated measured seconds; otherwise by the documented rows-moved
# heuristics below, exactly as in the PR-5 planner.
# ---------------------------------------------------------------------------

_HEURISTIC = {"kind": "heuristic"}


def _agg_cost_class(aggs) -> str:
    """Calibration bucket of a (possibly fused) pass: the members' shared
    ``cost_class`` when they agree, else the generic tables."""
    classes = {getattr(a, "cost_class", "generic") for a in aggs}
    return classes.pop() if len(classes) == 1 else "generic"


def _measured_costs(cand_keys: Mapping[str, str], agg_cls: str, rows: int,
                    groups: int | None = None):
    """``(costs_in_seconds, source)`` from the active calibration, or
    None unless EVERY candidate is covered — measured seconds must never
    rank against heuristic row counts in one comparison."""
    cal = _calibration.current()
    if cal is None:
        return None
    costs = {}
    for cand, key in cand_keys.items():
        s = cal.engine_seconds(key, agg_cls, rows, groups)
        if s is None:
            return None
        costs[cand] = s
    return costs, {"kind": "measured", "backend": cal.backend,
                   "timestamp": cal.timestamp}


def _mesh_segments(mesh, row_axes) -> int:
    if mesh is None:
        return 1
    axes = tuple(row_axes or ("data",))
    return int(np.prod([mesh.shape[a] for a in axes]))


def scan_cost(engine: str, rows: int, segs: int = 1) -> float:
    """Estimated rows-moved cost of a one-pass scan.

    ``local`` folds every row in one program; a distributed table pays a
    gather first when it spans more than one segment.  ``sharded`` is the
    two-phase pattern: each segment folds its chunk, plus one merge
    collective per segment.  At ``segs == 1`` the tie breaks to local
    (the merge term), which is also the numerically identical choice.
    """
    if engine == "local":
        return float(rows) * (2.0 if segs > 1 else 1.0)
    if engine == "sharded":
        return math.ceil(rows / segs) + segs
    raise ValueError(f"scan_cost: unknown engine {engine!r}")


def grouped_cost(method: str, rows: int, groups: int, block: int,
                 segs: int = 1) -> float:
    """Estimated cost of a grouped pass: the segment layout scans the
    group-aligned blocks once (padding bounded by one partial block per
    group); the masked fallback scans the full table once per group."""
    if method == "segment":
        base = rows + groups * block
    elif method == "masked":
        base = rows * groups
    else:
        raise ValueError(f"grouped_cost: unknown method {method!r}")
    if segs > 1:  # chunked across segments + G partials per-leaf collective
        return math.ceil(base / segs) + groups * segs
    return float(base)


def join_cost(strategy: str, fact_rows: int, dim_rows: int) -> float:
    """Estimated rows-moved cost of resolving ``fact ⋈ dim`` (on top of
    the grouped pass that consumes it).

    ``sort-share`` is the planned strategy: one argsort of the dimension
    key (amortized to its row count — and FREE when a GROUP BY already
    paid it, via the ``sort_permutation`` memo) plus one searchsorted
    gather producing a single int32 gid column over the fact rows.
    ``gather-materialize`` is the naive alternative it is priced
    against: gather the dimension attribute onto every fact row AND
    write a fresh joined copy of the fact columns — 2x the fact's rows
    moved, plus the same dimension sort, with no sort/scan sharing
    downstream (every statement re-pays it)."""
    if strategy == "sort-share":
        return float(fact_rows + dim_rows)
    if strategy == "gather-materialize":
        return float(2 * fact_rows + dim_rows)
    raise ValueError(f"join_cost: unknown strategy {strategy!r}")


def _capable(engine: str, *, mask: bool = False, group_by: bool = False,
             stream: bool = False) -> bool:
    """Capability-matrix filter: can ``engine`` honor what the statement
    needs?  (``sharded-grouped[segment]`` looks up ``sharded-grouped``.)"""
    caps = ENGINE_CAPS[engine.split("[")[0]]
    return ((not mask or caps["mask"])
            and (not group_by or caps["group_by"])
            and (not stream or caps["stream"]))


def select_scan_engine(rows: int, mesh=None, row_axes=None, *,
                       mask: bool = False, forced: str = "auto",
                       agg_cls: str = "generic"
                       ) -> tuple[str, dict[str, float], dict]:
    """Pick local vs sharded for a one-pass scan: candidates filtered
    through :data:`ENGINE_CAPS` by what the statement needs (``mask``),
    ranked by measured seconds when an active calibration covers every
    candidate (``agg_cls`` selects its bucket), else by the heuristic
    cost model.  Returns ``(engine, candidate_costs, cost_source)``."""
    segs = _mesh_segments(mesh, row_axes)
    candidates = ["local"] + (["sharded"] if mesh is not None else [])
    costs = {e: scan_cost(e, rows, segs) for e in candidates
             if _capable(e, mask=mask)}
    source = _HEURISTIC
    measured = _measured_costs({e: e for e in costs}, agg_cls, rows)
    if measured is not None:
        costs, source = measured
    if forced != "auto":
        if forced not in ("local", "sharded"):
            raise ValueError(f"unknown scan engine {forced!r}")
        if forced == "sharded" and mesh is None:
            forced = "local"  # graceful degrade, like run_sharded itself
        return forced, costs, source
    return min(costs, key=lambda e: costs[e]), costs, source


def select_grouped_method(rows: int, groups: int, *, segment_ok: bool,
                          block_size: int | None = None, segs: int = 1,
                          mask: bool = False, forced: str = "auto",
                          agg_cls: str = "generic"
                          ) -> tuple[str, dict[str, float], dict]:
    """Pick segment vs masked for a grouped pass: both candidates must
    clear the capability matrix (group_by + the statement's mask need);
    the generic-merge fallback (``segment_ok=False``) removes the
    segment candidate.  Ranking prefers measured seconds (calibration
    keys ``[sharded-]grouped-<method>``) when available, like
    :func:`select_scan_engine`."""
    bs = segment_block_size(rows, groups, block_size)
    costs = {}
    for method in (("segment",) if segment_ok else ()) + ("masked",):
        if _capable(f"grouped-{method}", mask=mask, group_by=True):
            costs[method] = grouped_cost(method, rows, groups, bs, segs)
    source = _HEURISTIC
    prefix = "sharded-grouped-" if segs > 1 else "grouped-"
    measured = _measured_costs({m: prefix + m for m in costs}, agg_cls,
                               rows, groups)
    if measured is not None:
        costs, source = measured
    if forced != "auto":
        if forced == "segment" and not segment_ok:
            raise ValueError(
                "method='segment' forced on a generic-merge aggregate "
                "(agg.segment_ops() is None); use 'masked'")
        if forced not in ("segment", "masked"):
            raise ValueError(f"unknown grouped method {forced!r}")
        return forced, costs, source
    return min(costs, key=lambda m: costs[m]), costs, source


# ---------------------------------------------------------------------------
# Physical passes.
# ---------------------------------------------------------------------------

def _mask_key(mask) -> Any:
    """Fusion identity of a base mask.  Masks are compared by object
    identity — two equal-content arrays planned apart stay apart (safe:
    never fuses statements whose filters could differ)."""
    return None if mask is None else id(mask)


def node_tables(node) -> tuple[Table, ...]:
    """Every base :class:`Table` a statement READS — the structural
    multi-table check behind the result cache's single-table contract.
    A join reads two (fact first — the admission/windowing table); a
    prebuilt GroupedView resolves to its data table; streams read none.
    Any future multi-table node must surface all of its tables here, so
    the cache rejection in :func:`semantic_fingerprint` is inherited
    instead of re-discovered."""
    join = getattr(node, "join", None)
    if join is not None:
        return (join.fact, join.dim)
    t = getattr(node, "table", None)
    if isinstance(t, GroupedView):
        t = t.table
    return (t,) if isinstance(t, Table) else ()


def statement_fingerprint(node) -> tuple:
    """Stable identity of a retained statement's physical shape — what a
    :class:`~repro.core.materialize.MaterializedHandle` pins alongside
    the table version.  Two statements share a fingerprint iff refreshing
    one's retained state is valid for the other: same aggregate instance,
    projection, grouping, partitioning and engine knobs.  The table is
    deliberately NOT part of the fingerprint — the handle pins the table
    object itself and tracks its version separately."""
    proj = _normalize_projection(getattr(node, "columns", None))
    proj_key = None if proj is None else tuple(sorted(proj.items()))
    if isinstance(node, ScanAgg):
        return ("scan", id(node.agg), proj_key, _mask_key(node.mask),
                node.block_size, node.engine, node.jit)
    if isinstance(node, GroupedScanAgg):
        return ("grouped", id(node.agg), proj_key, node.group_col,
                node.num_groups, _mask_key(node.mask), node.block_size,
                node.method,
                id(node.mesh) if node.mesh is not None else None,
                tuple(node.row_axes) if node.row_axes else None, node.jit)
    raise TypeError(f"statement_fingerprint: not a retainable scan "
                    f"statement: {node!r}")


def semantic_fingerprint(node) -> tuple | None:
    """Cross-submitter identity of a statement's RESULT — the analytics
    server's cache key component (:mod:`repro.core.server`).

    Unlike :func:`statement_fingerprint` (which keys on aggregate object
    *identity* — right for a retained handle that owns its instances),
    this keys on the aggregate's :meth:`~Aggregate.cache_key`, so the
    same logical statement issued by two different sessions — each with
    its own freshly constructed aggregate — maps to ONE fingerprint.  Two
    statements share a semantic fingerprint iff executing either against
    the same (table id, table version) yields identical finalized
    results: same aggregate semantics, projection, grouping, block
    partitioning and engine knobs.  The table itself is NOT part of the
    fingerprint; the server keys its cache by
    ``(table id, table version, fingerprint)``.

    Returns ``None`` — never cache, always execute — when the statement
    cannot be identified semantically: an aggregate without a
    ``cache_key``, a masked statement (masks are session-local arrays,
    identity-keyed), a prebuilt :class:`GroupedView` (a snapshot with no
    version to track), a non-scan statement (fits and streams hold no
    cacheable table-version-addressed result), or — checked structurally
    via :func:`node_tables`, so future multi-table nodes inherit it — a
    statement reading MORE THAN ONE table.  The single-table restriction
    is a correctness wall, not a limitation to lift casually: the
    fingerprint is computed at SUBMIT time while the server probes its
    cache at DRAIN time against the base table's current version only,
    so version-keying a join on both tables at submit could still serve
    a result after the dimension alone mutated in between.  The refusal
    records a loud ``kind="cache_reject"`` trace event per statement
    (joined statements still execute — windowed by their fact table —
    they are just never cached or deduplicated).
    """
    tables = node_tables(node)
    if len(tables) > 1:
        _record("cache_reject", reason="multi-table",
                node=type(node).__name__,
                tables=tuple(id(t) for t in tables))
        return None
    if not isinstance(node, (ScanAgg, GroupedScanAgg)):
        return None
    agg_key = node.agg.cache_key()
    if agg_key is None or node.mask is not None:
        return None
    proj = _normalize_projection(node.columns)
    proj_key = None if proj is None else tuple(sorted(proj.items()))
    if isinstance(node, ScanAgg):
        return ("scan", agg_key, proj_key, node.block_size, node.engine,
                node.jit)
    if isinstance(node.table, GroupedView):
        return None
    return ("grouped", agg_key, proj_key, node.group_col, node.num_groups,
            node.block_size, node.method,
            id(node.mesh) if node.mesh is not None else None,
            tuple(node.row_axes) if node.row_axes else None, node.jit)


@dataclasses.dataclass
class PhysicalPass:
    """One physical engine execution covering >= 1 statements."""

    kind: str                       # "scan" | "grouped" | "fit" | "stream"
    engine: str
    members: list                   # [(statement index, node), ...]
    cost: float | None
    info: dict                      # rendering details (explain)
    run: Callable[[], dict]         # -> {statement index: result}


def fused_scan_pass(members: Sequence[tuple[int, ScanAgg]], *,
                    engine: str = "auto") -> PhysicalPass:
    """Build ONE shared-scan pass from compatible ScanAgg statements.

    This is the loud guard of the mixed-mask correctness trap: a fused
    ``run_many`` applies one base mask to every member, so members whose
    table, mask or block partitioning differ are rejected with an error —
    never silently folded together.
    """
    nodes = [n for _, n in members]
    base = nodes[0]
    if any(n.table is not base.table for n in nodes):
        raise ValueError(
            "fused_scan_pass: statements scan different tables — "
            "cross-table fusion is not a shared scan")
    masks = {_mask_key(n.mask) for n in nodes}
    if len(masks) > 1:
        raise ValueError(
            "fused_scan_pass: mixed-mask fusion rejected — run_many "
            "applies ONE base mask to every fused aggregate, so fusing "
            "statements with different mask= would silently apply one "
            "statement's filter to the others; plan them as separate "
            "passes")
    if len({n.block_size for n in nodes}) > 1:
        raise ValueError(
            "fused_scan_pass: members use different block_size values — "
            "fusing them would change their fold partitioning (and "
            "bit-exactness) vs solo execution")
    if len({n.jit for n in nodes}) > 1:
        raise ValueError("fused_scan_pass: members disagree on jit=")

    rows = base.table.n_rows
    idx = [i for i, _ in members]
    aggs = [_member_agg(n) for n in nodes]
    eng, costs, source = select_scan_engine(
        rows, base.table.mesh, base.table.row_axes,
        mask=base.mask is not None,
        forced=base.engine if engine == "auto" else engine,
        agg_cls=_agg_cost_class(aggs))

    def run():
        out = run_many(aggs, base.table, block_size=base.block_size,
                       mask=base.mask, jit=base.jit, engine=eng)
        return dict(zip(idx, out))

    return PhysicalPass(
        kind="scan", engine=eng, members=list(members),
        cost=costs[eng],
        info={"table": base.table, "rows": rows, "mask": base.mask,
              "block_size": base.block_size, "costs": costs,
              "cost_source": source},
        run=run)


def _grouped_view(node) -> GroupedView:
    if isinstance(node.table, GroupedView):
        return node.table
    if node.group_col is None:
        raise ValueError("GroupedScanAgg needs group_col (or a "
                         "prebuilt GroupedView)")
    return node.table.group_by(node.group_col, node.num_groups)


def _resolve_groups(node) -> int:
    if isinstance(node.table, GroupedView):
        return node.table.num_groups
    if node.num_groups is not None:
        return int(node.num_groups)
    # re-planning the same statement (explain + run, bench reps): reuse
    # the memoized view's count instead of re-reducing the id column.
    # Goes through the version-checked accessor, so a view outdated by
    # Table.append / invalidate can never leak into the plan — appended
    # rows may introduce new group ids.
    view = node.table.cached_group_by(node.group_col, None)
    if view is not None:
        return view.num_groups
    gids = node.table[node.group_col].astype(jnp.int32)
    return int(jax.device_get(jnp.max(gids))) + 1


def fused_grouped_pass(members: Sequence[tuple[int, GroupedScanAgg]]
                       ) -> PhysicalPass:
    """ONE grouped pass (one sort, one partitioned scan) for compatible
    grouped statements.  Same loud-rejection contract as
    :func:`fused_scan_pass`."""
    nodes = [n for _, n in members]
    base = nodes[0]
    if any(n.table is not base.table for n in nodes):
        raise ValueError("fused_grouped_pass: statements group different "
                         "tables/views")
    if any(n.group_col != base.group_col for n in nodes):
        raise ValueError("fused_grouped_pass: statements group by "
                         "different key columns")
    if len({_mask_key(n.mask) for n in nodes}) > 1:
        raise ValueError(
            "fused_grouped_pass: mixed-mask fusion rejected — one base "
            "mask applies to every fused grouped aggregate")
    if len({(n.num_groups, n.block_size, n.method, id(n.mesh), n.jit)
            for n in nodes}) > 1:
        raise ValueError("fused_grouped_pass: members disagree on "
                         "num_groups/block_size/method/mesh/jit")

    base_tbl = base.table.table if isinstance(base.table, GroupedView) \
        else base.table
    mesh = base.mesh if base.mesh is not None else base_tbl.mesh
    segs = _mesh_segments(mesh, base.row_axes or base_tbl.row_axes)
    groups = _resolve_groups(base)
    rows = base.table.n_rows

    # A fused grouped pass takes the segment path only when EVERY member
    # is segment-reducible (one generic-merge member poisons the fused
    # state, exactly as FusedAggregate.segment_ops declares).
    data_cols = dict(base_tbl.columns)
    data_cols.pop(base.group_col, None)
    member_aggs = [_member_agg(n) for n in nodes]
    segment_ok = True
    for a in member_aggs:
        try:
            ok = probe_segment_ops(a, data_cols) is not None
        except Exception:
            ok = False
        segment_ok = segment_ok and ok
    method, costs, source = select_grouped_method(
        rows, groups, segment_ok=segment_ok, block_size=base.block_size,
        segs=segs, mask=base.mask is not None, forced=base.method,
        agg_cls=_agg_cost_class(member_aggs))

    engine = ("sharded-grouped[%s]" % method) if mesh is not None \
        else f"grouped-{method}"
    idx = [i for i, _ in members]
    projections = [_normalize_projection(n.columns) for n in nodes]

    def run():
        view = _grouped_view(base)
        if all(p is not None for p in projections):
            union = sorted({src for p in projections for src in p.values()})
            view = view.select(*union)
        fused = _fused_for(member_aggs)
        out = run_grouped(fused, view, block_size=base.block_size,
                          mask=base.mask, method=method, mesh=base.mesh,
                          row_axes=base.row_axes, jit=base.jit)
        return dict(zip(idx, out))

    return PhysicalPass(
        kind="grouped", engine=engine, members=list(members),
        cost=costs[method],
        info={"table": base_tbl, "group_col": base.group_col,
              "groups": groups, "rows": rows, "mask": base.mask,
              "costs": costs, "cost_source": source,
              "view_key": (id(base_tbl), base.group_col)},
        run=run)


def fused_join_pass(members: Sequence[tuple[int, "JoinedGroupedScanAgg"]]
                    ) -> PhysicalPass:
    """ONE joined-grouped pass — shared sort-merge key resolution + one
    partitioned segment scan — for compatible joined statements (the
    planner's first multi-table fusion).  Same loud-rejection contract
    as :func:`fused_grouped_pass`; join compatibility means the SAME
    (fact, dim, fact_key, dim_key, attr, on_missing) spec, compared by
    table identity like every fusion key."""
    nodes = [n for _, n in members]
    base = nodes[0]
    j = base.join
    if any(n.join.spec_key() != j.spec_key() for n in nodes):
        raise ValueError(
            "fused_join_pass: statements join different (fact, dim, key, "
            "attr) triples — cross-join fusion would mix unrelated "
            "group-id columns")
    if len({_mask_key(n.mask) for n in nodes}) > 1:
        raise ValueError(
            "fused_join_pass: mixed-mask fusion rejected — one base mask "
            "applies to every fused joined aggregate")
    if len({(n.num_groups, n.block_size, n.method, id(n.mesh), n.jit)
            for n in nodes}) > 1:
        raise ValueError("fused_join_pass: members disagree on "
                         "num_groups/block_size/method/mesh/jit")

    mesh = base.mesh if base.mesh is not None else j.fact.mesh
    segs = _mesh_segments(mesh, base.row_axes or j.fact.row_axes)
    groups = int(base.num_groups) if base.num_groups is not None \
        else j.attr_groups()
    rows = j.fact.n_rows

    # Segment reducibility is probed on the FACT's columns — the joined
    # table is exactly them plus the (stripped-at-group_by) gid column.
    member_aggs = [_member_agg(n) for n in nodes]
    segment_ok = True
    for a in member_aggs:
        try:
            ok = probe_segment_ops(a, dict(j.fact.columns)) is not None
        except Exception:
            ok = False
        segment_ok = segment_ok and ok
    method, costs, source = select_grouped_method(
        rows, groups, segment_ok=segment_ok, block_size=base.block_size,
        segs=segs, mask=base.mask is not None, forced=base.method,
        agg_cls=_agg_cost_class(member_aggs))

    join_costs = {s: join_cost(s, rows, j.dim.n_rows)
                  for s in ("sort-share", "gather-materialize")}
    # candidate costs include the key-resolution term, so the pass cost
    # equals its chosen candidate and explain's rejected-list stays honest
    costs = {m: c + join_costs["sort-share"] for m, c in costs.items()}
    engine = ("sharded-grouped[%s]" % method) if mesh is not None \
        else f"grouped-{method}"
    idx = [i for i, _ in members]
    projections = [_normalize_projection(n.columns) for n in nodes]

    def run():
        res = j.resolve()
        view = res.table.group_by(res.gid_col, groups)
        if all(p is not None for p in projections):
            union = sorted({src for p in projections for src in p.values()})
            view = view.select(*union)
        fused = _fused_for(member_aggs)
        out = run_grouped(fused, view, block_size=base.block_size,
                          mask=base.mask, method=method, mesh=base.mesh,
                          row_axes=base.row_axes, jit=base.jit)
        return dict(zip(idx, out))

    return PhysicalPass(
        kind="join", engine=engine, members=list(members),
        cost=costs[method],
        info={"table": j.fact, "group_col": j.attr_col, "groups": groups,
              "rows": rows, "mask": base.mask, "costs": costs,
              "cost_source": source,
              "join": {"dim": j.dim, "on": f"{j.fact_key}={j.dim_key}",
                       "on_missing": j.on_missing, "costs": join_costs},
              # one logical partitioning sort per star triple: joined
              # passes over the same spec share it (and explain counts
              # it once), exactly like grouped passes share a view_key
              "view_key": ("join",) + j.spec_key()},
        run=run)


def _fit_pass(index: int, node: IterativeFit) -> PhysicalPass:
    run_layout = node.layout  # what run() hands to fit_grouped
    if node.blocks is not None:
        engine, info = "stream", {}
    elif node.group_col is not None:
        layout = node.layout
        if layout == "auto":
            # Resolve the grouped layout once, at plan time (EXPLAIN
            # consults the task the way a DB consults statistics) and
            # hand the decision to fit_grouped so execution doesn't
            # re-probe.  A failing probe stays "auto": the plan renders
            # the layout as undecided and execution surfaces the real
            # error from fit_grouped instead of a masked mislabel.
            cols = {k: v for k, v in node.table.columns.items()
                    if k != node.group_col}
            try:
                s0 = jax.tree.map(jnp.asarray, node.task.init_state(cols))
                states0 = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (1,) + x.shape), s0)
                layout = "segment" if _segment_task_ok(
                    node.task, states0, cols) else "masked"
                run_layout = layout
            except Exception:
                layout = "auto"
        mesh = node.mesh if node.mesh is not None else node.table.mesh
        engine = ("sharded-grouped[%s]" % layout) if mesh is not None \
            else f"grouped-{layout}"
        info = {"table": node.table, "group_col": node.group_col,
                "groups": _resolve_groups(node),
                "view_key": (id(node.table), node.group_col)
                if layout == "segment" else None}
    else:
        mesh = node.mesh if node.mesh is not None else node.table.mesh
        engine = node.engine
        if engine == "auto":
            engine = "sharded" if mesh is not None else "local"
        info = {"table": node.table}

    rows = None if node.table is None else node.table.n_rows
    cost = None if rows is None else node.max_iters * float(rows)

    def run():
        if node.blocks is not None:
            res = fit_stream(node.task, node.blocks,
                             max_iters=node.max_iters, tol=node.tol,
                             warm_start=node.warm_start)
        elif node.group_col is not None:
            res = fit_grouped(node.task, node.table, node.group_col,
                              node.num_groups, max_iters=node.max_iters,
                              tol=node.tol, block_size=node.block_size,
                              mask=node.mask, warm_start=node.warm_start,
                              layout=run_layout, mesh=node.mesh,
                              row_axes=node.row_axes, jit=node.jit)
        else:
            res = fit(node.task, node.table, max_iters=node.max_iters,
                      tol=node.tol, engine=node.engine, mode=node.mode,
                      block_size=node.block_size, mask=node.mask,
                      warm_start=node.warm_start, mesh=node.mesh,
                      row_axes=node.row_axes, jit=node.jit)
        return {index: res}

    return PhysicalPass(
        kind="fit", engine=engine, members=[(index, node)], cost=cost,
        info=dict(info, rows=rows, max_iters=node.max_iters, tol=node.tol,
                  cost_source=_HEURISTIC),
        run=run)


def fused_stream_pass(members: Sequence[tuple[int, StreamAgg]]
                      ) -> PhysicalPass:
    nodes = [n for _, n in members]
    base = nodes[0]
    if any(n.blocks is not base.blocks for n in nodes):
        raise ValueError("fused_stream_pass: statements fold different "
                         "block streams")
    idx = [i for i, _ in members]

    def run():
        blocks = base.blocks() if callable(base.blocks) else base.blocks
        out = run_stream(_fused_for([_member_agg(n) for n in nodes]),
                         blocks)
        return dict(zip(idx, out))

    return PhysicalPass(kind="stream", engine="stream",
                        members=list(members), cost=None, info={}, run=run)


# ---------------------------------------------------------------------------
# The planner.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PhysicalPlan:
    passes: list[PhysicalPass]
    n_statements: int

    def execute(self) -> list:
        """Run every pass; results come back in statement order."""
        out: dict[int, Any] = {}
        for p in self.passes:
            out.update(p.run())
        return [out[i] for i in range(self.n_statements)]

    # -- EXPLAIN ----------------------------------------------------------
    def explain(self) -> str:
        tables: dict[int, str] = {}

        def tname(tbl) -> str:
            if tbl is None:
                return "-"
            return tables.setdefault(id(tbl), f"t{len(tables)}")

        # label tables in statement order for stable goldens (a join
        # pass names its dimension right after its fact)
        for p in self.passes:
            tname(p.info.get("table"))
            join = p.info.get("join")
            if join is not None:
                tname(join["dim"])

        shared_sorts = {}
        for p in self.passes:
            vk = p.info.get("view_key")
            if vk is not None:
                shared_sorts.setdefault(vk, []).append(p)
        n_sorts = len(shared_sorts)

        lines = [f"plan: {self.n_statements} statement"
                 f"{'s' if self.n_statements != 1 else ''} -> "
                 f"{len(self.passes)} pass"
                 f"{'es' if len(self.passes) != 1 else ''}"
                 + (f", {n_sorts} sort{'s' if n_sorts != 1 else ''}"
                    if n_sorts else "")]
        sort_ids = {vk: f"v{i}" for i, vk in enumerate(shared_sorts)}
        for k, p in enumerate(self.passes):
            info = p.info
            bits = [f"pass {k}: {_KIND_NAMES[p.kind]} [{p.engine}]"]
            if info.get("table") is not None:
                bits.append(tname(info["table"]))
            join = info.get("join")
            if join is not None:
                bits.append(f"JOIN {tname(join['dim'])} "
                            f"on {join['on']}"
                            + (f" on_missing={join['on_missing']}"
                               if join["on_missing"] != "error" else ""))
            if info.get("group_col"):
                bits.append(f"by {info['group_col']} "
                            f"groups={info['groups']}")
                vk = info.get("view_key")
                if vk is not None:
                    shared = len(shared_sorts[vk]) > 1
                    bits.append(f"sort={sort_ids[vk]}"
                                + ("(shared)" if shared else ""))
            if info.get("rows") is not None:
                bits.append(f"rows={info['rows']}")
            if p.kind == "fit":
                tol = info.get("tol")
                bits.append(f"max_iters={info['max_iters']} "
                            f"tol={'none' if tol is None else f'{tol:g}'}")
            if info.get("mask") is not None:
                bits.append("mask=yes")
            if info.get("block_size") is not None:
                bits.append(f"block={info['block_size']}")
            if p.cost is not None:
                src = info.get("cost_source") or _HEURISTIC
                measured = src.get("kind") == "measured"
                rejected = {e: c for e, c in info.get("costs", {}).items()
                            if c != p.cost}
                bits.append(f"cost={_fmt_cost(p.cost, measured)}")
                bits.append(f"[measured {src['backend']}@{src['timestamp']}]"
                            if measured else "[heuristic]")
                if rejected:
                    bits.append("(rejected: " + " ".join(
                        f"{e}={_fmt_cost(c, measured)}" for e, c in sorted(
                            rejected.items())) + ")")
                if join is not None:
                    jc = join["costs"]
                    bits.append(
                        "(join: sort-share="
                        f"{_fmt_cost(jc['sort-share'], False)} rejected "
                        "gather-materialize="
                        f"{_fmt_cost(jc['gather-materialize'], False)})")
            lines.append("  " + " ".join(bits))
            for i, n in p.members:
                label = n.label or f"s{i}"
                lines.append(f"    {label}: {type(n.agg).__name__}"
                             if hasattr(n, "agg") else
                             f"    {label}: {type(n.task).__name__}")
        return "\n".join(lines)


_KIND_NAMES = {"scan": "shared-scan", "grouped": "grouped-scan",
               "join": "join-grouped-scan", "fit": "fit",
               "stream": "stream-scan"}


def _fmt_cost(c: float, measured: bool) -> str:
    """Heuristic costs are dimensionless row counts (integers); measured
    costs are seconds and render with a unit."""
    if not measured:
        return str(int(c))
    return f"{c:.2f}s" if c >= 1.0 else f"{c * 1e3:.2f}ms"


def plan(statements: Sequence[Any]) -> PhysicalPlan:
    """Compile logical statements into a physical plan: fuse compatible
    scans, dedup sorts, select engines.  Pass order follows each pass's
    first statement; results are returned in statement order."""
    statements = list(statements)
    with _span("plan", statements=len(statements)):
        return _plan(statements)


def _plan(statements: list) -> PhysicalPlan:
    groups: dict[Any, list] = {}
    order: list[Any] = []
    for i, node in enumerate(statements):
        if isinstance(node, ScanAgg):
            key = ("scan", id(node.table), _mask_key(node.mask),
                   node.block_size, node.engine, node.jit)
        elif isinstance(node, GroupedScanAgg):
            key = ("grouped", id(node.table), node.group_col,
                   node.num_groups, _mask_key(node.mask), node.block_size,
                   node.method, id(node.mesh) if node.mesh is not None
                   else None, node.jit)
        elif isinstance(node, JoinedGroupedScanAgg):
            # multi-table fusion: keyed on the join SPEC (both tables by
            # identity + keys/attr/policy), so joined statements built
            # independently — even with distinct Join instances — fuse
            # into one shared-resolution pass
            key = (("join",) + node.join.spec_key()
                   + (node.num_groups, _mask_key(node.mask),
                      node.block_size, node.method,
                      id(node.mesh) if node.mesh is not None else None,
                      node.jit))
        elif isinstance(node, StreamAgg):
            key = ("stream", id(node.blocks))
        elif isinstance(node, IterativeFit):
            key = ("fit", i)  # fits never fuse
        else:
            raise TypeError(f"not a logical plan node: {node!r}")
        if key not in groups:
            order.append(key)
        groups.setdefault(key, []).append((i, node))

    passes = []
    for key in order:
        members = groups[key]
        kind = key[0]
        if kind == "scan":
            passes.append(fused_scan_pass(members))
        elif kind == "grouped":
            passes.append(fused_grouped_pass(members))
        elif kind == "join":
            passes.append(fused_join_pass(members))
        elif kind == "stream":
            passes.append(fused_stream_pass(members))
        else:
            (i, node), = members
            passes.append(_fit_pass(i, node))
    return PhysicalPlan(passes, len(statements))


def execute(node) -> Any:
    """Eagerly execute one logical statement through the planner — the
    single-statement path every method wrapper uses.  Engine selection
    (and the ``group_by`` sort memo) work exactly as in a batch."""
    return plan([node]).execute()[0]


def explain(statements) -> str:
    """``EXPLAIN`` for one statement or a batch — the physical plan the
    optimizer would run, without running it."""
    if not isinstance(statements, (list, tuple)):
        statements = [statements]
    return plan(statements).explain()
