"""Host-side execution tracing — the plan layer's observability hooks.

The scan-sharing optimizer's whole claim is "N statements, ONE data
pass"; this module is how that claim is *checked* instead of asserted.
Every execution engine records one event per physical data pass
(``kind="scan"``), :meth:`Table.group_by` records one event per
partitioning sort actually performed (``kind="sort"`` — cache hits are
silent), the iterative engines record one event per fit
(``kind="fit"``), and a materialized-handle refresh that folds only
appended rows records its pass as ``kind="delta"`` instead of a scan —
so tests can assert "this refresh did NOT rescan the table".
``tests/test_plan.py`` and ``benchmarks/bench_plan.py`` wrap executions
in :func:`trace_execution` and count.

Events are recorded host-side at engine entry (never inside a traced
function), so the counters see physical engine executions: a fused
``run_many`` pass is ONE scan event regardless of how many member
aggregates it folds, and a masked grouped pass is one event even though
its cost is O(G·n) — the cost difference lives in ``explain()``, the
event count in the trace.

The analytics server (:mod:`repro.core.server`) adds two serving-side
kinds so cross-session sharing is *asserted*, not timed:
``kind="admission"`` — one event per drained admission window, tagged
with its base table (``detail["table"]``), the window size, statements
actually planned (after result-cache hits and same-fingerprint dedup),
physical passes, ``scans_saved`` (scan statements submitted minus scan
passes executed minus any view answers that had to rescan), and the
window's ``opened_at`` / ``drained_at`` monotonic timestamps +
``latency`` — per-table isolation ("a slow drain on table A did not
delay table B") is asserted from these timestamps, never from
wall-clock heuristics; and ``kind="cache_hit"`` — one event per
statement answered from the version-keyed result cache or a registered
materialized view, carrying ``detail["refresh"]`` with the honest
refresh kind (``"none"``/``"noop"``/``"delta"`` cost zero scans;
``"rescan"`` means the view re-read the table inside the hit path).
:meth:`Trace.summary` rolls every kind up into counts, plus a
per-table breakdown of the serving events under ``"by_table"``.

The join layer adds two more kinds.  ``kind="join"`` — one event per
sort-merge key resolution actually performed (:meth:`repro.core.join
.Join.resolve`; memo hits are silent, like ``group_by``), so "N joined
statements shared one resolution" is a trace count.  Every ``sort``
event carries ``detail["table"]`` (the sorting table's id) and
:meth:`Trace.summary` rolls sorts up per table under
``"sorts_by_table"`` — the assertion surface for sort dedup across a
star schema ("the dim key sort and the fact partition sort happened
once EACH"), counted, never timed.  ``kind="cache_reject"`` — one
event per statement the server-side result cache refused to fingerprint
because it reads MORE THAN ONE table (a join): the cache keys on a
single table's version, so caching a join result could serve stale
state after only the dimension mutated; the loud event makes the
refusal observable (see :func:`repro.core.plan.semantic_fingerprint`).

**Spans.**  :func:`span` is :func:`record` with an extent: a context
manager that records one event when it opens and stamps it with
``t0_ns`` / ``t1_ns`` (``time.perf_counter_ns()``) and ``parent``, the
index in :attr:`Trace.events` of the span that encloses it on the same
thread (``None`` at the top; the server runs statements on its drain
thread, so each thread keeps its own stack).  Point events from
:func:`record` leave all three ``None``.  The grouped statement path
opens ``run`` (:meth:`Session.run`) ⊃ ``plan`` (:func:`plan`),
``sort`` and ``group_by`` (memo misses of :meth:`Table.sort_permutation`
and :meth:`Table.group_by`), ``layout.index`` and ``layout.gather``
(:meth:`GroupedView.aligned_blocks`: the host's block index and its
upload, then the window copy's dispatch, with ``detail["blocks"]`` the
window-copied blocks and ``detail["row_gathered"]`` the rows that took
an element gather instead, none today) and ``fold.dispatch`` (kernel resolution,
prepared-program lookup and the call; ``detail["prepared"]`` is
``"hit"`` or ``"miss"``).  :meth:`Trace.spans` lists them and
:meth:`Trace.summary` sums their seconds per kind under ``"span_s"``.
Every span also opens ``jax.profiler.TraceAnnotation("madjax.<kind>")``,
so a profiler trace shows it on the host's timeline beside the device's
programs; inside the programs, ``jax.named_scope`` names ``madjax.fold``,
``madjax.finalize`` and ``madjax.merge`` do that job.  Spans are always
on: with no active trace and no profiler a span costs the annotation's
check and nothing else.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Iterator

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Event:
    kind: str               # points: "scan" | "fit" | "delta" | "kernel"
    #                       | "admission" | "cache_hit" | "join"
    #                       | "cache_reject"; spans: "run" | "plan"
    #                       | "sort" | "group_by" | "layout.index"
    #                       | "layout.gather" | "fold.dispatch"
    engine: str | None      # "local" / "sharded" / "grouped-segment" / ...;
    # for kind="kernel" this is the RESOLVED implementation ("ref" /
    # "pallas"), with detail carrying the kernel name and requested impl
    detail: dict[str, Any]
    t0_ns: int | None = None    # span opened (perf_counter_ns); None: point
    t1_ns: int | None = None    # span closed; None while open
    parent: int | None = None   # index of the enclosing span's event


class Trace:
    """An ordered list of engine events, with kind-filtered views."""

    def __init__(self):
        self.events: list[Event] = []

    def _kind(self, kind: str) -> list[Event]:
        return [e for e in self.events if e.kind == kind]

    @property
    def scans(self) -> list[Event]:
        return self._kind("scan")

    @property
    def sorts(self) -> list[Event]:
        return self._kind("sort")

    @property
    def fits(self) -> list[Event]:
        return self._kind("fit")

    @property
    def deltas(self) -> list[Event]:
        return self._kind("delta")

    @property
    def kernels(self) -> list[Event]:
        """Kernel dispatch resolutions — one per physical execution that
        consulted the registry; ``engine`` is the resolved impl."""
        return self._kind("kernel")

    @property
    def admissions(self) -> list[Event]:
        """Admission-window drains — one per drained per-table window
        (however triggered: count threshold, timeout, flush, demand, or
        the background drainer); ``detail`` carries the base table id,
        window size, planned/deduped/cache-hit statement counts,
        ``scans_saved``, and the ``opened_at``/``drained_at``/``latency``
        timestamps isolation assertions are built from."""
        return self._kind("admission")

    @property
    def joins(self) -> list[Event]:
        """Sort-merge join key resolutions actually performed
        (``Join.resolve`` memo misses; hits are silent) — N joined
        statements over one (fact, dim, key) triple record ONE."""
        return self._kind("join")

    @property
    def cache_rejects(self) -> list[Event]:
        """Statements the semantic fingerprint refused to identify for
        the result cache because they read more than one table;
        ``detail["tables"]`` lists the table ids involved."""
        return self._kind("cache_reject")

    @property
    def cache_hits(self) -> list[Event]:
        """Statements answered from the server's version-keyed result
        cache (``detail["source"] == "cache"``) or a registered
        materialized view (``"view"``).  ``detail["refresh"]`` says what
        the answer really cost: ``"none"``/``"noop"``/``"delta"`` cost
        zero physical scans, ``"rescan"`` re-read the table inside the
        hit path."""
        return self._kind("cache_hit")

    def spans(self, kind: str | None = None) -> list[Event]:
        """Span events (those with a ``t0_ns``), of one ``kind`` or all,
        in the order they opened."""
        return [e for e in self.events if e.t0_ns is not None
                and (kind is None or e.kind == kind)]

    def summary(self) -> dict:
        """Counts per event kind, seconds per span kind under
        ``"span_s"`` (closed spans; a nested span counts in its own kind
        and inside its parent's), plus the admission windows' aggregate
        sharing tallies (``scans_saved`` / ``deduped`` summed across
        windows) — what benches and serving logs print.  When admission
        events are present, ``out["by_table"]`` breaks the serving
        tallies down per base table (keyed by the admission events'
        ``detail["table"]`` id): windows drained, statements admitted,
        scans saved, dedups and cache hits — the cross-table rollup for
        per-table admission windows."""
        out: dict[str, Any] = {}
        span_s: dict[str, float] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
            if e.t0_ns is not None and e.t1_ns is not None:
                span_s[e.kind] = (span_s.get(e.kind, 0.0)
                                  + (e.t1_ns - e.t0_ns) / 1e9)
        if span_s:
            out["span_s"] = span_s
        sorts = self._kind("sort")
        if sorts:
            # per-table sort rollup: sort dedup across a star schema
            # ("one argsort per (table, key)") is asserted from these
            # counts, never from timing
            by_sorts: dict[Any, int] = {}
            for e in sorts:
                t = e.detail.get("table")
                by_sorts[t] = by_sorts.get(t, 0) + 1
            out["sorts_by_table"] = by_sorts
        admissions = self._kind("admission")
        for field in ("scans_saved", "deduped"):
            total = sum(e.detail.get(field, 0) for e in admissions)
            if total:
                out[field] = total
        if admissions:
            by: dict[Any, dict[str, int]] = {}
            for e in admissions:
                row = by.setdefault(e.detail.get("table"), {
                    "windows": 0, "statements": 0, "scans_saved": 0,
                    "deduped": 0, "cache_hits": 0})
                row["windows"] += 1
                row["statements"] += e.detail.get("window", 0)
                row["scans_saved"] += e.detail.get("scans_saved", 0)
                row["deduped"] += e.detail.get("deduped", 0)
                row["cache_hits"] += e.detail.get("cache_hits", 0)
            out["by_table"] = by
        return out


_ACTIVE: list[Trace] = []
# guards _ACTIVE, and an event's append with the index it gets
_LOCK = threading.Lock()
_THREAD = threading.local()    # .stack: {trace: event index} per open span


def record(kind: str, engine: str | None = None, **detail: Any) -> None:
    """Record one event on every active trace (no-op when none are)."""
    if not _ACTIVE:
        return
    with _LOCK:
        for t in _ACTIVE:
            t.events.append(Event(kind, engine, detail))


class span:
    """Record one timed event of ``kind`` on every active trace for the
    extent of a ``with`` block, and open
    ``jax.profiler.TraceAnnotation("madjax.<kind>")`` for the same
    extent::

        with span("layout.index", rows=n) as sp:
            ...
            sp.detail["blocks"] = nb     # shared by every trace's event

    The event's ``parent`` is the enclosing span's index in the same
    trace, from a per-thread stack.  Host-side only: a span opened
    inside a traced (jitted, vmapped, shard-mapped) function would time
    the tracing, not the work; inside a program use ``jax.named_scope``.
    """

    __slots__ = ("kind", "engine", "detail", "_note", "_opened")

    def __init__(self, kind: str, engine: str | None = None,
                 **detail: Any):
        self.kind = kind
        self.engine = engine
        self.detail = detail
        self._opened: list[Event] | None = None

    def __enter__(self) -> "span":
        self._note = TraceAnnotation(f"madjax.{self.kind}")
        self._note.__enter__()
        if not _ACTIVE:
            return self
        stack = _THREAD.__dict__.setdefault("stack", [])
        parents = stack[-1] if stack else {}
        opened, at = [], {}
        t0 = time.perf_counter_ns()
        with _LOCK:
            for t in _ACTIVE:
                ev = Event(self.kind, self.engine, self.detail, t0, None,
                           parents.get(t))
                t.events.append(ev)
                opened.append(ev)
                at[t] = len(t.events) - 1
        stack.append(at)
        self._opened = opened
        return self

    def __exit__(self, *exc) -> None:
        if self._opened is not None:
            t1 = time.perf_counter_ns()
            _THREAD.stack.pop()
            for ev in self._opened:
                ev.t1_ns = t1
        self._note.__exit__(*exc)


@contextlib.contextmanager
def trace_execution() -> Iterator[Trace]:
    """Collect engine events for the dynamic extent of the block::

        with trace_execution() as t:
            session.run()
        assert len(t.scans) == 1

    Nestable; every active trace sees every event.
    """
    t = Trace()
    with _LOCK:
        _ACTIVE.append(t)
    try:
        yield t
    finally:
        with _LOCK:
            _ACTIVE.remove(t)
