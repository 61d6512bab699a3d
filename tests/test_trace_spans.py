"""Timed spans on the grouped statement path (:func:`repro.core.trace.span`).

A grouped statement through the ``Session`` sugar records ``run`` and,
inside it, ``plan``, ``layout.index``, ``layout.gather`` and
``fold.dispatch``, each with its parent and its clock readings; the
partition sort's ``sort`` and ``group_by`` spans appear on memo misses
only; every span reaches the profiler's host plane as ``madjax.<kind>``;
and the programs carry the ``madjax.fold`` / ``madjax.finalize`` scopes
without being renamed.
"""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Session, Table, trace_execution
from repro.core.aggregates import _segment_jit, probe_segment_ops
from repro.core.trace import record, span
from repro.methods.linregr import LinregrAggregate

ROOT = Path(__file__).resolve().parents[1]
G = 5


def _table(n: int = 600, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    return Table.from_columns({
        "x": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=n), jnp.float32),
        "g": jnp.asarray(rng.integers(0, G, n), jnp.int32)})


def _grouped(table: Table, agg=None):
    s = Session()
    h = s.grouped_scan(agg if agg is not None else LinregrAggregate(),
                       table, "g", G, columns={"x": "x", "y": "y"})
    s.run()
    return h.result()


def test_a_grouped_statement_records_its_spans_under_run():
    t = _table()
    with trace_execution() as tr:
        _grouped(t)
    runs = tr.spans("run")
    assert len(runs) == 1
    i_run = tr.events.index(runs[0])
    run = runs[0]
    assert run.parent is None
    for kind in ("plan", "layout.index", "layout.gather", "fold.dispatch"):
        (ev,) = tr.spans(kind)
        assert ev.parent == i_run, kind
        assert run.t0_ns <= ev.t0_ns <= ev.t1_ns <= run.t1_ns, kind
    # the memo misses nest too: sort inside group_by inside run
    (gb,) = tr.spans("group_by")
    (srt,) = tr.spans("sort")
    assert gb.parent == i_run
    assert srt.parent == tr.events.index(gb)
    # point events keep no clock and no parent
    assert all(e.t0_ns is None and e.t1_ns is None and e.parent is None
               for e in tr.scans)
    assert set(tr.summary()["span_s"]) == {
        "run", "plan", "group_by", "sort", "layout.index",
        "layout.gather", "fold.dispatch"}
    assert all(v >= 0 for v in tr.summary()["span_s"].values())


def test_sort_and_group_by_spans_only_on_memo_misses():
    t = _table()
    with trace_execution() as tr:
        _grouped(t)
    assert len(tr.spans("sort")) == 1 and len(tr.spans("group_by")) == 1
    assert len(tr.sorts) == 1
    assert tr.summary()["sorts_by_table"] == {id(t): 1}
    with trace_execution() as tr:
        _grouped(t)
    assert tr.spans("sort") == [] and tr.spans("group_by") == []
    extra = _table(40, seed=1)
    t.append(dict(extra.columns))
    with trace_execution() as tr:
        _grouped(t)
    assert len(tr.spans("sort")) == 1 and len(tr.spans("group_by")) == 1


def test_fold_dispatch_says_whether_the_prepared_program_was_found():
    t = _table()
    agg = LinregrAggregate()
    with trace_execution() as tr:
        _grouped(t, agg)
    assert [e.detail["prepared"] for e in tr.spans("fold.dispatch")] == [
        "miss"]
    with trace_execution() as tr:
        _grouped(t, agg)
    assert [e.detail["prepared"] for e in tr.spans("fold.dispatch")] == [
        "hit"]
    with trace_execution() as tr:
        _grouped(t)                     # a new aggregate: a new program
    assert [e.detail["prepared"] for e in tr.spans("fold.dispatch")] == [
        "miss"]


def test_spans_keep_a_parent_stack_per_thread():
    seen = {}

    def other():
        with span("other"):
            pass

    with trace_execution() as tr:
        with span("outer"):
            th = threading.Thread(target=other)
            th.start()
            th.join()
            with span("inner"):
                pass
    for e in tr.spans():
        seen[e.kind] = e.parent
    assert seen == {"outer": None, "other": None, "inner": 0}


def test_a_span_with_no_trace_records_nothing():
    with span("outer") as sp:           # no trace active: nothing kept
        sp.detail["x"] = 1              # detail stays writable
        with trace_execution() as tr:
            with span("inner"):
                pass
    assert [(e.kind, e.parent) for e in tr.events] == [("inner", None)]


def test_spans_reach_the_profiler_host_plane(tmp_path):
    sys.path.insert(0, str(ROOT / "bench"))
    from benchlib import devtrace
    t = _table()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(_grouped(t))
    finally:
        jax.profiler.stop_trace()
    names = {n for n, _, _ in devtrace.reduce_xplane(str(tmp_path))["host"]}
    assert {"madjax.run", "madjax.plan", "madjax.layout.index",
            "madjax.layout.gather", "madjax.fold.dispatch"} <= names


@pytest.mark.parametrize("finalize", [True, False])
def test_the_segment_program_carries_named_scopes(finalize):
    view = _table().group_by("g", G)
    cols, valid, bgids = view.aligned_blocks(64)
    agg = LinregrAggregate()
    ops = probe_segment_ops(agg, dict(view.table.columns))
    fn, prepared = _segment_jit(agg, ops, G, finalize, ("test",))
    assert prepared == "miss"
    lowered = fn.lower(cols, valid, bgids)
    text = lowered.as_text(debug_info=True)
    assert "madjax.fold" in text
    assert ("madjax.finalize" in text) == finalize
    assert "@jit_go_segment" in lowered.as_text()


def test_parents_stay_right_with_many_threads():
    """More threads than cores open nested spans and record points into
    one trace under a short switch interval: every child's parent is the
    span its own thread opened around it."""
    threads, rounds = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for r in range(rounds):
                with span("outer", who=i, r=r):
                    record("point", who=i)
                    with span("inner", who=i, r=r):
                        pass

        with trace_execution() as tr:
            pool = [threading.Thread(target=work, args=(i,))
                    for i in range(threads)]
            for th in pool:
                th.start()
            for th in pool:
                th.join(timeout=60)
        assert not any(th.is_alive() for th in pool)
    finally:
        sys.setswitchinterval(old)
    inner = tr.spans("inner")
    assert len(inner) == len(tr.spans("outer")) == threads * rounds
    assert len(tr.events) == 3 * threads * rounds
    for ev in inner:
        parent = tr.events[ev.parent]
        assert parent.kind == "outer"
        assert (parent.detail["who"], parent.detail["r"]) == (
            ev.detail["who"], ev.detail["r"])
        assert parent.t0_ns <= ev.t0_ns <= ev.t1_ns <= parent.t1_ns
