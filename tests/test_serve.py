"""Analytics server: cross-session scan sharing + version-keyed caching.

The contract under test (``core/server.py`` + ``Session(server=...)``):

* Statements submitted by DIFFERENT sessions inside one admission window
  plan as one cross-session batch: compatible scans fuse into ONE
  physical pass, and same-fingerprint statements deduplicate to one
  member — trace events (``kind="scan"`` / ``"admission"``) assert the
  sharing structurally, no timing involved.
* The result cache is keyed ``(table id, table version, semantic
  fingerprint)`` and probed at DRAIN time, never at admission: a repeat
  statement against an unchanged table executes ZERO scans with a
  bit-identical result; a table mutated between admission and execution
  (append or invalidate) can never satisfy a stale entry — mutation
  hooks evict eagerly AND the version bump misses every old key, so the
  window replans and matches a fresh solo run bitwise.
* Living views registered with the server answer matching statements
  from their retained fold state (delta-refreshed across appends) and
  report their refresh kind honestly — an invalidated table's view
  answer is a RESCAN, visible in the trace and excluded from
  ``scans_saved``.
* Regression: ``Session.run()`` on an empty batch returns ``[]`` and
  ``Session.explain()`` returns ``"(empty batch)"`` — both modes.

Serving hardening (per-table windows + background drain):

* Statements partition into PER-TABLE admission windows; each drains
  independently, with its own ``admission`` trace event and a
  cross-table ``Trace.summary()["by_table"]`` rollup.
* ``drain="thread"`` gives liveness without traffic: a submitted
  statement resolves on ``window_timeout`` with NO subsequent
  submit/poll/result call (observed via the passive ``handle.wait()``).
* Execution runs OFF the admission lock: submits complete while a drain
  executes, a slow statement on table A never delays table B, and
  ``result(timeout=...)`` stays bounded even when another thread's
  in-flight drain holds the table's drain lock.  The slow statements in
  these tests are DETERMINISTICALLY slow — an eager (``jit=False``)
  transition gated on a ``threading.Event`` — never sleeps-and-hopes.
* ``MaterializedHandle`` is internally locked: concurrent refreshes
  cannot double-fold a delta, and a mutation racing a fold leaves the
  handle stale (pinned at the version it actually saw), not wrong.
"""

import gc
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    AnalyticsServer, GroupedScanAgg, ScanAgg, Session, Table, execute,
    trace_execution,
)
from repro.core.aggregates import MERGE_SUM, Aggregate
from repro.core.materialize import materialize
from repro.core.plan import semantic_fingerprint
from repro.core.templates import ProfileAggregate
from repro.methods.linregr import LinregrAggregate
from repro.methods.naive_bayes import NaiveBayesAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate

from strategies import Draw, cases, group_layout


def _dyadic_table(draw: Draw, n: int, d: int = 3, groups: int = 4):
    gids, _ = group_layout(draw, n, groups)
    return Table.from_columns({
        "x": draw.dyadic((n, d)),
        "y": draw.dyadic((n,)),
        "item": draw.ints((n,), 0, 40),
        "g": gids,
    })


def _delta_cols(draw: Draw, m: int, d: int = 3, groups: int = 4):
    return {
        "x": draw.dyadic((m, d)),
        "y": draw.dyadic((m,)),
        "item": draw.ints((m,), 0, 40),
        "g": draw.ints((m,), 0, groups - 1),
    }


def _bitwise_equal(a, b) -> bool:
    fa = [np.asarray(x) for x in jax.tree.leaves(a)]
    fb = [np.asarray(x) for x in jax.tree.leaves(b)]
    return len(fa) == len(fb) and all(
        x.shape == y.shape and (x == y).all() for x, y in zip(fa, fb))


class _GatedAggregate(Aggregate):
    """A deterministically slow aggregate: its transition blocks on an
    Event.  Run eagerly (``jit=False`` + ``block_size=None`` -> ONE
    Python-level ``transition`` call), the gate genuinely stalls the
    executing drain — no sleeps, no timing guesses."""

    merge_ops = MERGE_SUM

    def __init__(self, started: threading.Event | None = None,
                 release: threading.Event | None = None):
        self.started = started    # set when the fold begins executing
        self.release = release    # the fold waits for this

    def init(self, block):
        return jnp.zeros((), dtype=jnp.float32)

    def transition(self, state, block, mask):
        if self.started is not None:
            self.started.set()
        if self.release is not None:
            assert self.release.wait(60), "gated transition never released"
        return state + jnp.sum(jnp.where(mask, block["y"], 0.0))


def _gated_node(table, started=None, release=None):
    return ScanAgg(_GatedAggregate(started, release), table,
                   columns=("y",), engine="local", jit=False)


@pytest.fixture()
def table():
    d = Draw(7)
    return _dyadic_table(d, 512)


# ---------------------------------------------------------------------------
# Cross-session admission-window sharing
# ---------------------------------------------------------------------------

class TestWindowSharing:
    def test_cross_session_statements_fuse_into_one_scan(self, table):
        srv = AnalyticsServer(window_size=64)
        sessions = [Session(server=srv) for _ in range(4)]
        hs = []
        with trace_execution() as t:
            for s in sessions:
                hs.append(s.linregr(table))
                hs.append(s.countmin_sketch(table))
            srv.flush()
        # 8 statements from 4 sessions: ONE physical pass
        assert len(t.scans) == 1
        assert len(t.admissions) == 1
        ev = t.admissions[0].detail
        assert ev["window"] == 8 and ev["passes"] == 1
        assert ev["scans_saved"] == 7
        solo = execute(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        for h in hs[::2]:
            assert _bitwise_equal(h.result().coef, solo.coef)
        srv.close()

    def test_identical_statements_dedup_to_one_member(self, table):
        srv = AnalyticsServer(window_size=64)
        sessions = [Session(server=srv) for _ in range(6)]
        hs = [s.fm_distinct_count(table) for s in sessions]
        with trace_execution() as t:
            srv.flush()
        # six submitters, ONE planned statement (fingerprints match even
        # though every session built its own FMAggregate instance)
        assert t.admissions[0].detail["planned"] == 1
        assert t.admissions[0].detail["deduped"] == 5
        vals = [float(h.result()) for h in hs]
        assert len(set(vals)) == 1
        srv.close()

    def test_count_threshold_auto_drains(self, table):
        srv = AnalyticsServer(window_size=2)
        s1, s2 = Session(server=srv), Session(server=srv)
        h1 = s1.linregr(table)
        assert not h1.done() and srv.pending == 1
        h2 = s2.countmin_sketch(table)      # hits window_size -> drain
        assert h1.done() and h2.done() and srv.pending == 0
        srv.close()

    def test_timeout_drains_at_next_submit(self, table):
        srv = AnalyticsServer(window_size=1024, window_timeout=0.0)
        s = Session(server=srv)
        h1 = s.linregr(table)
        # timeout 0: the window is already overdue at the NEXT admission
        h2 = s.fm_distinct_count(table)
        assert h1.done()
        assert srv.poll() >= 0  # poll drains any overdue remainder
        h2.result()
        srv.close()

    def test_demand_execution_via_result(self, table):
        srv = AnalyticsServer(window_size=1024)
        s = Session(server=srv)
        h = s.linregr(table)
        assert not h.done()
        solo = execute(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        assert _bitwise_equal(h.result().coef, solo.coef)  # drains
        srv.close()

    def test_session_run_gathers_own_handles(self, table):
        srv = AnalyticsServer(window_size=1024)
        s1, s2 = Session(server=srv), Session(server=srv)
        s1.linregr(table)
        other = s2.fm_distinct_count(table)
        out = s1.run()
        assert len(out) == 1        # only s1's statements
        assert other.done()         # but the shared window drained
        srv.close()

    def test_profile_derived_handle(self, table):
        srv = AnalyticsServer(window_size=1024)
        s = Session(server=srv)
        h = s.profile(table, distinct_counts=True)
        stats = h.result()
        solo = execute(ScanAgg(ProfileAggregate(), table))
        assert _bitwise_equal(stats["x"]["sum"], solo["x"]["sum"])
        srv.close()

    def test_threaded_submitters_one_window(self, table):
        srv = AnalyticsServer(window_size=1024)
        results = [None] * 8

        def worker(i):
            s = Session(server=srv)
            results[i] = s.linregr(table).result(timeout=60)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        with trace_execution() as t:
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        solo = execute(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        for r in results:
            assert _bitwise_equal(r.coef, solo.coef)
        # every drain shares: total physical scans <= windows drained,
        # and at most one window actually planned anything
        assert len(t.scans) <= len(t.admissions)
        srv.close()


# ---------------------------------------------------------------------------
# Version-keyed result cache
# ---------------------------------------------------------------------------

class TestResultCache:
    def test_repeat_statement_zero_scans_bit_identical(self, table):
        srv = AnalyticsServer(window_size=64)
        s1, s2 = Session(server=srv), Session(server=srv)
        first = s1.countmin_sketch(table)
        srv.flush()
        with trace_execution() as t:
            again = s2.countmin_sketch(table)
            srv.flush()
        assert len(t.scans) == 0
        assert len(t.cache_hits) == 1
        assert t.cache_hits[0].detail["source"] == "cache"
        assert _bitwise_equal(first.result(), again.result())
        srv.close()

    def test_grouped_statement_caches_with_zero_sorts(self, table):
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        node = GroupedScanAgg(NaiveBayesAggregate(2), table, "g", 4,
                              columns=("x", "y"))
        h1 = s.statement(node)
        srv.flush()
        node2 = GroupedScanAgg(NaiveBayesAggregate(2), table, "g", 4,
                               columns=("x", "y"))
        with trace_execution() as t:
            h2 = s.statement(node2)
            srv.flush()
        assert len(t.scans) == 0 and len(t.sorts) == 0
        assert len(t.cache_hits) == 1
        assert _bitwise_equal(h1.result().mean, h2.result().mean)
        srv.close()

    def test_append_evicts_and_replans(self, table):
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        s.countmin_sketch(table)
        srv.flush()
        table.append(_delta_cols(Draw(11), 64))
        assert srv.stats["evicted"] >= 1
        with trace_execution() as t:
            h = s.countmin_sketch(table)
            srv.flush()
        assert len(t.scans) == 1 and len(t.cache_hits) == 0
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), table,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)
        srv.close()

    def test_masked_statements_bypass_cache(self, table):
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        mask = np.arange(table.n_rows) < 100
        n1 = ScanAgg(LinregrAggregate(), table, columns=("x", "y"),
                     mask=jax.numpy.asarray(mask))
        assert semantic_fingerprint(n1) is None
        h1 = s.statement(n1)
        srv.flush()
        with trace_execution() as t:
            h2 = s.statement(
                ScanAgg(LinregrAggregate(), table, columns=("x", "y"),
                        mask=jax.numpy.asarray(mask)))
            srv.flush()
        assert len(t.scans) == 1 and len(t.cache_hits) == 0
        assert _bitwise_equal(h1.result().coef, h2.result().coef)
        srv.close()

    def test_lru_bound_holds(self, table):
        srv = AnalyticsServer(window_size=1, cache_entries=2)
        s = Session(server=srv)
        s.linregr(table)
        s.countmin_sketch(table)
        s.fm_distinct_count(table)
        assert len(srv._cache) <= 2
        srv.close()

    def test_clear_cache_forces_rescan(self, table):
        srv = AnalyticsServer(window_size=1)
        s = Session(server=srv)
        s.linregr(table)
        srv.clear_cache()
        with trace_execution() as t:
            s.linregr(table)
        assert len(t.scans) == 1 and len(t.cache_hits) == 0
        srv.close()


# ---------------------------------------------------------------------------
# Mutation-vs-window races (seeded)
# ---------------------------------------------------------------------------

class TestMutationRaces:
    def test_append_lands_between_admission_and_drain(self):
        for draw in cases(6, base_seed=21):
            tbl = _dyadic_table(draw, 256)
            srv = AnalyticsServer(window_size=1024)
            s = Session(server=srv)
            s.linregr(tbl)
            srv.flush()                      # warm the cache @ version 0
            h = s.linregr(tbl)               # admitted @ version 0 ...
            tbl.append(_delta_cols(draw, draw.integers(8, 64)))
            with trace_execution() as t:
                srv.flush()                  # ... drained @ version 1
            # the warm entry is dead: no hit, a real scan, and the result
            # is bit-identical to a fresh solo run over the grown table
            assert len(t.cache_hits) == 0
            assert len(t.scans) == 1
            fresh = execute(ScanAgg(LinregrAggregate(), tbl,
                                    columns=("x", "y")))
            assert _bitwise_equal(h.result().coef, fresh.coef)
            srv.close()

    def test_invalidate_lands_between_admission_and_drain(self):
        for draw in cases(6, base_seed=22):
            tbl = _dyadic_table(draw, 256)
            srv = AnalyticsServer(window_size=1024)
            s = Session(server=srv)
            s.countmin_sketch(tbl)
            srv.flush()
            h = s.countmin_sketch(tbl)
            tbl.columns["item"] = jax.numpy.asarray(
                draw.ints((tbl.n_rows,), 0, 40))
            tbl.invalidate()
            with trace_execution() as t:
                srv.flush()
            assert len(t.cache_hits) == 0 and len(t.scans) == 1
            fresh = execute(ScanAgg(CountMinAggregate(4, 1024), tbl,
                                    columns=("item",)))
            assert _bitwise_equal(h.result(), fresh)
            srv.close()

    def test_fill_skipped_when_table_moves_during_execution(self, table):
        # simulate a concurrent writer landing DURING the drain: patch
        # the plan execution to append mid-flight; the post-execute fill
        # must skip (version moved past the plan-time stamp), so the next
        # probe replans instead of serving a result computed over
        # ambiguous rows
        import repro.core.server as server_mod
        srv = AnalyticsServer(window_size=1024)
        s = Session(server=srv)
        h = s.linregr(table)
        real_plan = server_mod.plan

        def racing_plan(nodes):
            pl = real_plan(nodes)
            real_execute = pl.execute

            def execute_and_mutate():
                out = real_execute()
                table.append(_delta_cols(Draw(3), 16))
                return out
            pl.execute = execute_and_mutate
            return pl

        server_mod.plan = racing_plan
        try:
            srv.flush()
        finally:
            server_mod.plan = real_plan
        assert len(srv._cache) == 0        # fill skipped, eviction fired
        with trace_execution() as t:
            h3 = s.linregr(table)
            srv.flush()
        assert len(t.cache_hits) == 0 and len(t.scans) == 1
        fresh = execute(ScanAgg(LinregrAggregate(), table,
                                columns=("x", "y")))
        assert _bitwise_equal(h3.result().coef, fresh.coef)
        srv.close()


# ---------------------------------------------------------------------------
# Living views as cache fillers
# ---------------------------------------------------------------------------

class TestViewFillers:
    def test_view_answers_matching_statement(self, table):
        srv = AnalyticsServer(window_size=64)
        owner = Session(server=srv)
        owner.materialize(ScanAgg(CountMinAggregate(4, 1024), table,
                                  columns=("item",)))
        other = Session(server=srv)
        with trace_execution() as t:
            h = other.countmin_sketch(table)
            srv.flush()
        assert len(t.scans) == 0
        assert t.cache_hits[0].detail["source"] == "view"
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), table,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)
        srv.close()

    def test_view_delta_refreshes_across_append(self, table):
        srv = AnalyticsServer(window_size=64)
        owner = Session(server=srv)
        owner.materialize(ScanAgg(CountMinAggregate(4, 1024), table,
                                  columns=("item",)))
        table.append(_delta_cols(Draw(5), 64))
        other = Session(server=srv)
        with trace_execution() as t:
            h = other.countmin_sketch(table)
            srv.flush()
        # answered by the view via a DELTA fold: zero full scans, and
        # the hit says so (refresh kind rides on the trace event)
        assert len(t.scans) == 0 and len(t.deltas) == 1
        assert t.cache_hits[0].detail["refresh"] == "delta"
        assert t.admissions[0].detail["scans_saved"] == 1
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), table,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)
        srv.close()

    def test_view_rescan_is_not_a_scan_saved(self, table):
        # REGRESSION (zero-scans mislabel): after invalidate() the
        # view's answer performs a FULL RESCAN inside the hit path; the
        # hit must say refresh="rescan", the scan must be visible in the
        # trace, and the admission window must NOT count it saved.
        srv = AnalyticsServer(window_size=64)
        owner = Session(server=srv)
        owner.materialize(ScanAgg(CountMinAggregate(4, 1024), table,
                                  columns=("item",)))
        table.columns["item"] = jax.numpy.asarray(
            Draw(9).ints((table.n_rows,), 0, 40))
        table.invalidate()
        other = Session(server=srv)
        with trace_execution() as t:
            h = other.countmin_sketch(table)
            srv.flush()
        hit = t.cache_hits[0].detail
        assert hit["source"] == "view" and hit["refresh"] == "rescan"
        assert len(t.scans) == 1            # the rescan is VISIBLE
        ev = t.admissions[0].detail
        assert ev["scans_saved"] == 0 and ev["view_rescans"] == 1
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), table,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)
        srv.close()


# ---------------------------------------------------------------------------
# Empty batches, errors, lifecycle
# ---------------------------------------------------------------------------

class TestEmptyBatchRegression:
    def test_local_run_empty_returns_empty_list(self):
        assert Session().run() == []

    def test_local_explain_empty(self):
        assert Session().explain() == "(empty batch)"

    def test_server_run_empty_returns_empty_list(self):
        srv = AnalyticsServer()
        assert Session(server=srv).run() == []
        srv.close()

    def test_server_explain_empty(self):
        srv = AnalyticsServer()
        assert Session(server=srv).explain() == "(empty batch)"
        srv.close()

    def test_flush_empty_returns_zero(self):
        srv = AnalyticsServer()
        assert srv.flush() == 0
        srv.close()

    def test_run_twice_second_empty(self, table):
        s = Session()
        s.linregr(table)
        assert len(s.run()) == 1
        assert s.run() == []


class TestLifecycle:
    def test_error_propagates_to_every_handle(self, table):
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        good = s.linregr(table)
        bad = s.statement(ScanAgg(LinregrAggregate(), table,
                                  columns={"x": "missing", "y": "y"}))
        with pytest.raises(Exception):
            srv.flush()
        with pytest.raises(RuntimeError):
            bad.result(timeout=1)
        with pytest.raises(RuntimeError):
            good.result(timeout=1)
        srv.close()

    def test_failing_post_fails_only_its_handle(self, table):
        # REGRESSION (cross-handle error leak): submitter B's failing
        # post callback used to re-raise out of flush() — so submitter
        # A, who merely triggered the drain, saw B's exception even
        # though A's own statement resolved fine.  The error belongs to
        # B's handle ALONE.
        srv = AnalyticsServer(window_size=64)
        sa, sb = Session(server=srv), Session(server=srv)
        good = sa.linregr(table)

        def boom(raw):
            raise ValueError("bad post")
        bad = sb.statement(ScanAgg(FMAggregate(item_col="item"), table,
                                   columns=("item",)), post=boom)
        srv.flush()                         # does NOT raise B's error
        assert good.done()
        good.result()                       # A is untouched by B's post
        with pytest.raises(RuntimeError) as err:
            bad.result(timeout=1)
        assert isinstance(err.value.__cause__, ValueError)
        srv.close()

    def test_result_timeout(self, table):
        srv = AnalyticsServer(window_size=64)
        h = srv.submit(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        # flush resolves on demand, so a timeout only fires for a handle
        # whose window already failed-and-cleared; emulate by resolving
        # through a fresh event that never fires
        h._event.clear()
        h._server = _NeverFlush()
        with pytest.raises(TimeoutError):
            h.result(timeout=0.05)
        srv.close()

    def test_result_timeout_bounded_by_inflight_drain(self):
        # REGRESSION: result(timeout=t) used to call flush() with no
        # bound, so it blocked for as long as another thread's in-flight
        # drain of the same table held the lock — the timeout never even
        # started.  The deadline must cover lock acquisition + wait.
        d = Draw(33)
        ta = _dyadic_table(d, 128)
        started, release = threading.Event(), threading.Event()
        srv = AnalyticsServer(window_size=1024)
        srv.submit(_gated_node(ta, started, release))
        flusher = threading.Thread(target=srv.flush, daemon=True)
        flusher.start()
        assert started.wait(30)             # the drain is executing
        try:
            hb = Session(server=srv).linregr(ta)   # same table, pending
            t0 = time.monotonic()
            with pytest.raises(TimeoutError):
                hb.result(timeout=0.3)
            assert time.monotonic() - t0 < 10.0    # bounded, not stuck
        finally:
            release.set()
        flusher.join(30)
        hb.result(timeout=30)               # drains fine once unblocked
        srv.close()

    def test_result_skips_flush_when_done(self, table):
        # REGRESSION: result() on an ALREADY-RESOLVED handle used to
        # trigger a demand flush anyway — here that flush would stall on
        # the gated statement; skipping it returns instantly.
        srv = AnalyticsServer(window_size=1024)
        h = srv.submit(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        srv.flush()
        assert h.done()
        release = threading.Event()
        pending = srv.submit(_gated_node(table, None, release))
        t0 = time.monotonic()
        h.result(timeout=0.5)               # no drain: instant
        assert time.monotonic() - t0 < 5.0
        assert not pending.done()
        release.set()
        srv.close()                         # drains the gated statement
        assert pending.done()

    def test_close_deregisters_hooks(self, table):
        srv = AnalyticsServer(window_size=1)
        s = Session(server=srv)
        s.linregr(table)
        srv.close()
        evicted = srv.stats["evicted"]
        table.append(_delta_cols(Draw(6), 8))
        assert srv.stats["evicted"] == evicted  # hook is gone
        assert not table._mutation_hooks

    def test_explain_renders_window(self, table):
        srv = AnalyticsServer(window_size=1024)
        s1, s2 = Session(server=srv), Session(server=srv)
        s1.linregr(table)
        s2.linregr(table)
        s2.countmin_sketch(table)
        text = srv.explain()
        assert "3 submitted" in text and "1 deduped" in text
        assert "shared-scan" in text
        srv.flush()
        srv.close()

    def test_trace_summary_counts(self, table):
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        with trace_execution() as t:
            s.linregr(table)
            s.countmin_sketch(table)
            srv.flush()
            s.linregr(table)
            srv.flush()
        summ = t.summary()
        assert summ["admission"] == 2
        assert summ["cache_hit"] == 1
        assert summ["scans_saved"] >= 1
        srv.close()


# ---------------------------------------------------------------------------
# Background drain thread + per-table windows
# ---------------------------------------------------------------------------

class TestDrainThread:
    def test_timeout_fires_without_traffic(self, table):
        # LIVENESS: with drain="thread", a submitted statement resolves
        # with NO subsequent submit/poll/result call — handle.wait() is
        # purely passive.
        srv = AnalyticsServer(window_size=1024, window_timeout=0.05,
                              drain="thread")
        s = Session(server=srv)
        h = s.linregr(table)
        assert h.wait(30)                   # background drainer fired
        solo = execute(ScanAgg(LinregrAggregate(), table,
                               columns=("x", "y")))
        assert _bitwise_equal(h.result(timeout=1).coef, solo.coef)
        srv.close()

    def test_count_threshold_drains_in_background(self, table):
        srv = AnalyticsServer(window_size=2, drain="thread")
        s1, s2 = Session(server=srv), Session(server=srv)
        h1 = s1.linregr(table)
        h2 = s2.countmin_sketch(table)      # hits window_size -> wake
        assert h1.wait(30) and h2.wait(30)
        srv.close()

    def test_slow_table_does_not_delay_other_table(self):
        # PER-TABLE ISOLATION: table A's drain is stuck executing a
        # gated statement; table B's statement, submitted afterwards,
        # resolves while A is still blocked.  Asserted structurally
        # (B done, A not) and from the per-table admission events.
        d = Draw(31)
        ta = _dyadic_table(d, 256)
        tb = _dyadic_table(d, 256)
        started, release = threading.Event(), threading.Event()
        srv = AnalyticsServer(window_size=1, drain="thread")
        try:
            with trace_execution() as t:
                ha = srv.submit(_gated_node(ta, started, release))
                assert started.wait(30)     # A's drain is executing
                hb = Session(server=srv).linregr(tb)
                assert hb.wait(30)          # B drains during A's stall
                assert not ha.done()
                t_b_done = time.monotonic()
                release.set()
                assert ha.wait(30)
            by_table = {e.detail["table"]: e.detail for e in t.admissions}
            assert set(by_table) == {id(ta), id(tb)}
            # B's window drained while A's statement was still executing
            assert by_table[id(tb)]["drained_at"] < t_b_done
            summ = t.summary()
            assert set(summ["by_table"]) == {id(ta), id(tb)}
            assert summ["by_table"][id(tb)]["windows"] == 1
        finally:
            release.set()
            srv.close()

    def test_submit_nonblocking_during_inflight_drain(self):
        # a submit — even on the SAME table — returns while that table's
        # drain is executing; the refill re-check drains it afterwards
        d = Draw(32)
        ta = _dyadic_table(d, 256)
        started, release = threading.Event(), threading.Event()
        srv = AnalyticsServer(window_size=1, drain="thread")
        try:
            srv.submit(_gated_node(ta, started, release))
            assert started.wait(30)
            t0 = time.monotonic()
            h2 = Session(server=srv).linregr(ta)
            assert time.monotonic() - t0 < 5.0      # admission only
            assert not h2.done()
            release.set()
            assert h2.wait(30)              # drained by the refill loop
        finally:
            release.set()
            srv.close()

    def test_demand_mode_submit_nonblocking_while_flush_executes(self):
        # same property without the drainer: another thread's flush()
        # holds table A's drain; submits (A and B) stay non-blocking
        d = Draw(34)
        ta, tb = _dyadic_table(d, 128), _dyadic_table(d, 128)
        started, release = threading.Event(), threading.Event()
        srv = AnalyticsServer(window_size=1024)
        srv.submit(_gated_node(ta, started, release))
        flusher = threading.Thread(target=srv.flush, daemon=True)
        flusher.start()
        assert started.wait(30)
        try:
            t0 = time.monotonic()
            sa, sb = Session(server=srv), Session(server=srv)
            ha, hb = sa.linregr(ta), sb.linregr(tb)
            assert time.monotonic() - t0 < 5.0
            hb.result(timeout=30)           # B drains independently
            assert not ha.done()            # A's drain lock is held
        finally:
            release.set()
        flusher.join(30)
        ha.result(timeout=30)
        srv.close()

    def test_poisoned_statement_does_not_kill_drainer(self, table):
        srv = AnalyticsServer(window_size=1, drain="thread")
        bad = srv.submit(ScanAgg(LinregrAggregate(), table,
                                 columns={"x": "missing", "y": "y"}))
        assert bad.wait(30)
        with pytest.raises(RuntimeError):
            bad.result(timeout=1)
        good = Session(server=srv).linregr(table)   # drainer survived
        assert good.wait(30)
        assert srv.stats["drain_errors"] >= 1
        srv.close()

    def test_close_stops_drainer(self, table):
        srv = AnalyticsServer(window_size=1024, window_timeout=0.05,
                              drain="thread")
        h = Session(server=srv).linregr(table)
        srv.close()
        assert h.done()                     # close() drains remainder
        assert not srv._drainer.is_alive()


class TestPerTableWindows:
    def test_windows_partition_by_table(self):
        d = Draw(35)
        ta, tb = _dyadic_table(d, 128), _dyadic_table(d, 128)
        srv = AnalyticsServer(window_size=3)
        s = Session(server=srv)
        s.linregr(ta)
        s.countmin_sketch(ta)
        hb = s.linregr(tb)
        # tb's window holds ONE statement: ta filling ITS window to the
        # count threshold must not drain tb's
        ha = s.fm_distinct_count(ta)        # ta hits window_size=3
        assert ha.done() and not hb.done()
        assert srv.pending == 1
        srv.flush()
        assert hb.done()
        srv.close()

    def test_per_table_admission_events_and_rollup(self):
        d = Draw(36)
        ta, tb = _dyadic_table(d, 128), _dyadic_table(d, 128)
        srv = AnalyticsServer(window_size=64)
        s = Session(server=srv)
        with trace_execution() as t:
            s.linregr(ta)
            s.countmin_sketch(ta)
            s.linregr(tb)
            srv.flush()
        assert len(t.admissions) == 2       # one drain event PER TABLE
        by = t.summary()["by_table"]
        assert by[id(ta)]["statements"] == 2
        assert by[id(tb)]["statements"] == 1
        assert all("latency" in e.detail and "drained_at" in e.detail
                   for e in t.admissions)
        srv.close()


# ---------------------------------------------------------------------------
# Size/cost-aware cache admission (GDSF)
# ---------------------------------------------------------------------------

class TestCachePolicy:
    def test_byte_budget_holds(self, table):
        # three float results of ~identical size against a budget that
        # fits only two -> the resident set stays under budget
        srv = AnalyticsServer(window_size=1, cache_bytes=100)
        with srv._lock:
            for i in range(3):
                srv._cache_put((i, 0, ("fp",)),
                               np.zeros(5, np.float64), cost=1.0)  # 40 B
        assert srv._cache_used <= 100 and len(srv._cache) == 2
        assert srv.stats["cache_evicted"] == 1
        srv.close()

    def test_huge_cheap_result_cannot_flush_small_expensive_ones(self):
        srv = AnalyticsServer(cache_bytes=1000)
        with srv._lock:
            for i in range(10):             # 10 small, expensive entries
                srv._cache_put((i, 0, ("small",)),
                               np.zeros(1, np.float64), cost=1e6)
            # one huge CHEAP result: admitting it must not evict the
            # valuable small set — GDSF evicts the lowest cost/byte
            # priority first, which is the giant itself
            srv._cache_put((99, 0, ("huge",)),
                           np.zeros(120, np.float64), cost=1.0)
        assert all((i, 0, ("small",)) in srv._cache for i in range(10))
        assert (99, 0, ("huge",)) not in srv._cache
        srv.close()

    def test_oversized_result_rejected_outright(self):
        srv = AnalyticsServer(cache_bytes=64)
        with srv._lock:
            srv._cache_put((0, 0, ("big",)), np.zeros(100, np.float64))
        assert len(srv._cache) == 0
        assert srv.stats["cache_rejected"] == 1
        srv.close()

    def test_entry_count_bound_still_holds(self, table):
        srv = AnalyticsServer(cache_entries=2)
        with srv._lock:
            for i in range(5):
                srv._cache_put((i, 0, ("fp",)), np.zeros(1, np.float64))
        assert len(srv._cache) <= 2
        srv.close()


# ---------------------------------------------------------------------------
# Weak table hooks — a long-lived server must not pin dead tables
# ---------------------------------------------------------------------------

class TestWeakHooks:
    def test_dead_table_auto_purges(self):
        # REGRESSION (strong-ref leak): the server used to hold hooked
        # tables forever; now a collected table's hook, cache entries
        # and window vanish with it — and because entries die WITH the
        # table, a recycled id() can never match a stale cache key.
        srv = AnalyticsServer(window_size=1)
        tbl = _dyadic_table(Draw(13), 128)
        tid = id(tbl)
        Session(server=srv).linregr(tbl)    # drains + fills the cache
        assert tid in srv._hooked
        assert any(k[0] == tid for k in srv._cache)
        del tbl
        gc.collect()
        assert tid not in srv._hooked
        assert not any(k[0] == tid for k in srv._cache)
        assert tid not in srv._windows
        srv.close()

    def test_live_table_keeps_hook_and_cache(self, table):
        srv = AnalyticsServer(window_size=1)
        Session(server=srv).linregr(table)
        gc.collect()
        assert id(table) in srv._hooked     # weak, but alive
        with trace_execution() as t:
            Session(server=srv).linregr(table)
        assert len(t.cache_hits) == 1       # cache survives gc
        srv.close()


# ---------------------------------------------------------------------------
# MaterializedHandle thread safety
# ---------------------------------------------------------------------------

class TestMaterializeThreadSafety:
    def _gated_run_local(self, monkeypatch, started, release):
        import importlib
        mat = importlib.import_module("repro.core.materialize")
        real = mat.run_local

        def gated(*args, **kwargs):
            started.set()
            assert release.wait(60)
            return real(*args, **kwargs)
        monkeypatch.setattr(mat, "run_local", gated)

    def test_concurrent_refresh_folds_delta_once(self, monkeypatch):
        # REGRESSION: two concurrent refreshes used to BOTH pass the
        # version check and fold the same delta twice (double-merge).
        # With the internal lock: exactly ONE delta fold.
        d = Draw(17)
        tbl = _dyadic_table(d, 256)
        h = materialize(ScanAgg(CountMinAggregate(4, 1024), tbl,
                                columns=("item",)))
        started, release = threading.Event(), threading.Event()
        self._gated_run_local(monkeypatch, started, release)
        tbl.append(_delta_cols(d, 64))
        with trace_execution() as t:
            threads = [threading.Thread(target=h.result)
                       for _ in range(2)]
            for th in threads:
                th.start()
            assert started.wait(30)         # first refresh inside fold
            release.set()
            for th in threads:
                th.join(30)
        assert len(t.deltas) == 1           # second refresh was a noop
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), tbl,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)

    def test_delta_vs_rescan_race_stays_correct(self, monkeypatch):
        # an invalidate() landing WHILE a delta fold executes: the delta
        # pins the version it observed (stale), so the next read rescans
        # — never a delta merged on top of rows it did not see
        d = Draw(19)
        tbl = _dyadic_table(d, 256)
        h = materialize(ScanAgg(CountMinAggregate(4, 1024), tbl,
                                columns=("item",)))
        started, release = threading.Event(), threading.Event()
        self._gated_run_local(monkeypatch, started, release)
        tbl.append(_delta_cols(d, 64))
        refresher = threading.Thread(target=h.refresh, daemon=True)
        refresher.start()
        assert started.wait(30)             # delta fold in flight ...
        tbl.columns["item"] = jax.numpy.asarray(
            d.ints((tbl.n_rows,), 0, 40))
        tbl.invalidate()                    # ... and the table moves
        release.set()
        refresher.join(30)
        assert h.stale()                    # pinned at the OLD version
        assert h.refresh() == "rescan"
        fresh = execute(ScanAgg(CountMinAggregate(4, 1024), tbl,
                                columns=("item",)))
        assert _bitwise_equal(h.result(), fresh)


class _NeverFlush:
    def flush(self, timeout=None):
        return 0


def test_analytics_serve_demo_reraises_session_errors(monkeypatch):
    """The serving demo fails loudly when an analyst thread raises: the
    error reaches the caller (and the CLI's exit code), not just stderr."""
    from repro.launch import analytics_serve

    def boom(*args, **kwargs):
        raise ZeroDivisionError("analyst")

    monkeypatch.setattr(analytics_serve, "_analyst_round", boom)
    with pytest.raises(RuntimeError, match="analyst sessions failed") as ei:
        analytics_serve.serve_analytics(rows=64, dims=2, sessions=2,
                                        rounds=1)
    assert isinstance(ei.value.__cause__, ZeroDivisionError)
