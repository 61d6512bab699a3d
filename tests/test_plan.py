"""Logical-plan layer: scan sharing, sort dedup, cost-based engine
selection, loud mixed-mask rejection, golden EXPLAIN plans, and planned
vs per-statement-direct parity (bit-identical for exact-state
aggregates).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ENGINE_CAPS, GroupedScanAgg, ProfileAggregate, ScanAgg, Session,
    StreamAgg, Table, execute, plan, run_grouped, run_local,
    trace_execution,
)
from repro.core.plan import (
    fused_scan_pass, select_grouped_method, select_scan_engine,
)
from repro.methods.linregr import LinregrAggregate
from repro.methods.quantiles import HistogramAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate

N, GROUPS = 512, 4


@pytest.fixture(scope="module")
def table(key):
    kx, ky, ki = jax.random.split(key, 3)
    return Table.from_columns({
        "x": jax.random.normal(kx, (N, 3)),
        "y": jax.random.normal(ky, (N,)),
        "item": jax.random.randint(ki, (N,), 0, 100),
        "g": (jnp.arange(N) % GROUPS).astype(jnp.int32),
    })


def _cm():
    return CountMinAggregate(depth=4, width=256, item_col="item")


def _fm():
    return FMAggregate(num_hashes=4, bits=16, item_col="item")


def _hist():
    return HistogramAggregate(-4.0, 4.0, bins=64, value_col="y")


# -- capability matrix & cost model -------------------------------------------

def test_capability_matrix_shape():
    assert set(ENGINE_CAPS) == {
        "local", "sharded", "stream", "grouped-segment", "grouped-masked",
        "sharded-grouped"}
    for caps in ENGINE_CAPS.values():
        assert set(caps) == {"mask", "group_by", "fit", "stream"}
    assert not ENGINE_CAPS["stream"]["mask"]
    assert ENGINE_CAPS["sharded-grouped"]["group_by"]


class _FakeMesh:
    def __init__(self, segs):
        self.shape = {"data": segs}


def test_select_scan_engine_cost_based():
    eng, costs, src = select_scan_engine(100_000, mesh=None)
    assert eng == "local" and set(costs) == {"local"}
    assert src == {"kind": "heuristic"}  # no calibration active
    # >1 segment: the two-phase sharded plan is strictly cheaper
    eng, costs, _ = select_scan_engine(100_000, mesh=_FakeMesh(4))
    assert eng == "sharded"
    assert costs["sharded"] < costs["local"]
    # degenerate 1-segment mesh: tie breaks to the local fold
    eng, _, _ = select_scan_engine(100_000, mesh=_FakeMesh(1))
    assert eng == "local"
    # forced engine is honored, not re-derived
    eng, _, _ = select_scan_engine(100_000, mesh=_FakeMesh(4),
                                   forced="local")
    assert eng == "local"


def test_select_grouped_method_cost_based():
    m, costs, src = select_grouped_method(100_000, 64, segment_ok=True)
    assert m == "segment" and costs["segment"] < costs["masked"]
    assert src == {"kind": "heuristic"}
    m, costs, _ = select_grouped_method(100_000, 64, segment_ok=False)
    assert m == "masked" and "segment" not in costs
    with pytest.raises(ValueError, match="segment"):
        select_grouped_method(100_000, 64, segment_ok=False,
                              forced="segment")


# -- scan sharing across statements -------------------------------------------

def test_batch_three_statements_one_pass(table):
    """The acceptance criterion: >=3 independent one-pass statements over
    one table -> exactly ONE data pass, bit-identical to per-statement
    direct engine calls on exact-state aggregates."""
    sess = Session()
    h_cm = sess.scan(_cm(), table)
    h_fm = sess.scan(_fm(), table)
    h_hist = sess.scan(_hist(), table)
    with trace_execution() as t:
        sess.run()
    assert len(t.scans) == 1, [e.engine for e in t.scans]

    # per-statement direct engine execution (the pre-plan dataflow)
    solo_cm = run_local(_cm(), table)
    solo_fm = run_local(_fm(), table)
    solo_hist = run_local(_hist(), table)
    # integer sketch counters / bitmap states and histogram counts are
    # exact: planned fusion must be BIT-identical, not just close
    assert np.array_equal(np.asarray(h_cm.result()), np.asarray(solo_cm))
    assert float(h_fm.result()) == float(solo_fm)
    assert np.array_equal(np.asarray(h_hist.result()),
                          np.asarray(solo_hist))


def test_planned_profile_and_linregr_share_scan(table):
    sess = Session()
    h_prof = sess.profile(table.select("x", "y"))
    h_ols = sess.linregr(table)
    with trace_execution() as t:
        sess.run()
    # profile scans its own (projected) table; linregr scans `table` —
    # two tables, two passes, but profile's members still fuse
    assert len(t.scans) == 2
    prof = h_prof.result()
    solo = run_local(ProfileAggregate(), table.select("x", "y"))
    np.testing.assert_allclose(np.asarray(prof["y"]["mean"]),
                               np.asarray(solo["y"]["mean"]), rtol=1e-6)
    res = h_ols.result()
    from repro.methods.linregr import linregr
    solo_ols = linregr(table)
    np.testing.assert_allclose(np.asarray(res.coef),
                               np.asarray(solo_ols.coef), rtol=1e-6)


def test_projection_isolates_templated_members(table):
    """A fused ProfileAggregate member must profile exactly ITS
    statement's columns even when the fused block carries more."""
    sess = Session()
    h_prof = sess.scan(ProfileAggregate(), table, columns=("y",))
    h_cm = sess.scan(_cm(), table)
    with trace_execution() as t:
        sess.run()
    assert len(t.scans) == 1
    assert set(h_prof.result()) == {"y"}


# -- the mixed-mask correctness trap ------------------------------------------

def test_mixed_masks_plan_as_separate_passes(table):
    m1 = np.arange(N) % 2 == 0
    m2 = np.arange(N) % 3 == 0
    sess = Session()
    h1 = sess.scan(_hist(), table, mask=m1)
    h2 = sess.scan(_hist(), table, mask=m2)
    h3 = sess.scan(_hist(), table)  # no mask: its own pass too
    pl = plan(sess._nodes)
    assert len(pl.passes) == 3
    sess.run()
    for h, m in ((h1, m1), (h2, m2), (h3, None)):
        solo = run_local(_hist(), table, mask=None if m is None
                         else jnp.asarray(m))
        assert np.array_equal(np.asarray(h.result()), np.asarray(solo))


def test_mixed_mask_fusion_rejected_loudly(table):
    m1 = jnp.asarray(np.arange(N) % 2 == 0)
    m2 = jnp.asarray(np.arange(N) % 3 == 0)
    members = [(0, ScanAgg(_hist(), table, mask=m1)),
               (1, ScanAgg(_hist(), table, mask=m2))]
    with pytest.raises(ValueError, match="mixed-mask"):
        fused_scan_pass(members)


def test_cross_table_and_block_size_fusion_rejected(table, key):
    other = Table.from_columns({"y": jax.random.normal(key, (N,))})
    with pytest.raises(ValueError, match="different tables"):
        fused_scan_pass([(0, ScanAgg(_hist(), table)),
                         (1, ScanAgg(_hist(), other))])
    with pytest.raises(ValueError, match="block_size"):
        fused_scan_pass([(0, ScanAgg(_hist(), table, block_size=64)),
                         (1, ScanAgg(_hist(), table, block_size=128))])


# -- sort dedup ---------------------------------------------------------------

def test_grouped_statements_share_one_sort_and_scan(table):
    sess = Session()
    h_cm = sess.grouped_scan(_cm(), table, "g", columns=("item",))
    h_fm = sess.grouped_scan(_fm(), table, "g", columns=("item",))
    h_lr = sess.grouped_scan(LinregrAggregate(), table, "g",
                             columns=("x", "y"))
    with trace_execution() as t:
        sess.run()
    assert len(t.sorts) == 1, "N grouped statements must share ONE sort"
    assert len(t.scans) == 1, "compatible grouped statements must fuse"
    solo_cm = run_grouped(_cm(), table.select("item", "g"), "g", GROUPS)
    assert np.array_equal(np.asarray(h_cm.result()), np.asarray(solo_cm))
    solo_lr = run_grouped(LinregrAggregate(),
                          table.select("x", "y", "g"), "g", GROUPS)
    np.testing.assert_allclose(np.asarray(h_lr.result().coef),
                               np.asarray(solo_lr.coef),
                               rtol=1e-5, atol=1e-5)
    assert h_fm.result().shape == (GROUPS,)


def test_group_by_memo_across_plans_and_invalidate(table):
    tbl = Table.from_columns({k: v for k, v in table.columns.items()})
    with trace_execution() as t:
        execute(GroupedScanAgg(_cm(), tbl, "g", columns=("item",)))
        execute(GroupedScanAgg(_fm(), tbl, "g", columns=("item",)))
    assert len(t.sorts) == 1, "the group_by memo spans separate plans"
    assert tbl.group_by("g") is tbl.group_by("g", GROUPS)
    tbl.invalidate()
    with trace_execution() as t:
        tbl.group_by("g")
    assert len(t.sorts) == 1, "invalidate() must drop the memo"


def test_quantiles_grouped_single_sort(table):
    from repro.methods.quantiles import quantiles_grouped
    with trace_execution() as t:
        out = quantiles_grouped(table.select("y", "g").with_column(
            "v", table["y"]), "g", [0.25, 0.5, 0.75], bins=128)
    assert len(t.sorts) == 1
    assert len(t.scans) == 2  # range pass + histogram pass
    assert out.shape == (GROUPS, 3)


def test_sort_permutation_memo_spans_grouped_entry_points(table):
    """The hoisted ``Table.sort_permutation`` memo is shared by EVERY
    consumer of a table's partitioning sort: ``fit_grouped`` and a
    planned grouped scan over the same (table, key) pay ONE argsort;
    ``quantiles_grouped``'s two internal passes pay one more on its
    projection table; and ``Trace.summary()`` attributes each to its
    table in the ``sorts_by_table`` rollup."""
    from repro.core import fit_grouped
    from repro.methods.linregr import LinregrTask
    from repro.methods.quantiles import quantiles_grouped
    tbl = Table.from_columns({k: v for k, v in table.columns.items()})
    tbl = tbl.with_column("v", tbl["y"])
    with trace_execution() as t:
        quantiles_grouped(tbl, "g", [0.5], bins=64)
        fit_grouped(LinregrTask(), tbl, "g", GROUPS, max_iters=1, tol=None)
        execute(GroupedScanAgg(LinregrAggregate(), tbl, "g",
                               columns=("x", "y")))
    assert len(t.sorts) == 2, "one sort per (table, key), ever"
    by = t.summary()["sorts_by_table"]
    assert by[id(tbl)] == 1 and sorted(by.values()) == [1, 1]
    perm_a = tbl.sort_permutation("g")
    perm_b = tbl.sort_permutation("g")
    assert perm_a is perm_b, "memo must return the identical product"


# -- stream fusion ------------------------------------------------------------

def test_stream_statements_fuse_over_shared_source(table):
    blocks = iter([{"item": np.arange(100) % 30},
                   {"item": np.arange(100) % 60}])
    sess = Session()
    h_cm = sess.stream_scan(_cm(), blocks)
    h_fm = sess.stream_scan(_fm(), blocks)
    with trace_execution() as t:
        sess.run()
    # mandatory fusion: the shared iterator is consumed exactly once
    assert len(t.scans) == 1
    solo_tbl = Table.from_columns({"item": np.concatenate(
        [np.arange(100) % 30, np.arange(100) % 60])})
    assert np.array_equal(np.asarray(h_cm.result()),
                          np.asarray(run_local(_cm(), solo_tbl)))
    assert float(h_fm.result()) == float(run_local(_fm(), solo_tbl))


# -- fits through the plan layer ----------------------------------------------

def test_session_fit_matches_eager(two_tables=None):
    from repro.core import synthetic_classification_table
    from repro.methods.logregr import logregr
    tbl, _ = synthetic_classification_table(jax.random.PRNGKey(3), 2000, 4)
    sess = Session()
    h = sess.logregr(tbl, max_iters=8)
    with trace_execution() as t:
        sess.run()
    assert len(t.fits) == 1 and t.fits[0].engine == "local"
    eager = logregr(tbl, max_iters=8)
    np.testing.assert_allclose(np.asarray(h.result().coef),
                               np.asarray(eager.coef), rtol=1e-6)
    assert h.result().n_iters == eager.n_iters


def test_handle_before_run_raises(table):
    sess = Session()
    h = sess.scan(_cm(), table)
    with pytest.raises(RuntimeError, match="has not executed"):
        h.result()


# -- golden EXPLAIN plans -----------------------------------------------------

def test_explain_golden_fused_batch(table):
    sess = Session()
    sess.scan(_cm(), table)
    sess.scan(_fm(), table)
    sess.scan(_hist(), table)
    sess.grouped_scan(_cm(), table, "g", num_groups=GROUPS,
                      columns=("item",))
    sess.grouped_scan(_fm(), table, "g", num_groups=GROUPS,
                      columns=("item",))
    assert sess.explain() == (
        "plan: 5 statements -> 2 passes, 1 sort\n"
        "  pass 0: shared-scan [local] t0 rows=512 cost=512 [heuristic]\n"
        "    s0: CountMinAggregate\n"
        "    s1: FMAggregate\n"
        "    s2: HistogramAggregate\n"
        "  pass 1: grouped-scan [grouped-segment] t0 by g groups=4 "
        "sort=v0 rows=512 cost=1024 [heuristic] (rejected: masked=2048)\n"
        "    s3: CountMinAggregate\n"
        "    s4: FMAggregate"
    )


def test_explain_golden_masked_and_fit(table, key):
    mask = jnp.asarray(np.arange(N) % 2 == 0)
    from repro.methods.logregr import IRLSTask
    from repro.core import IterativeFit
    sess = Session()
    sess.scan(_hist(), table, mask=mask, block_size=128)
    sess.grouped_scan(_cm(), table, "g", num_groups=GROUPS,
                      columns=("item",), method="masked")
    sess.statement(IterativeFit(
        IRLSTask(), table.select("x", "y"), max_iters=5, tol=1e-4,
        label="irls"))
    assert sess.explain() == (
        "plan: 3 statements -> 3 passes, 1 sort\n"
        "  pass 0: shared-scan [local] t0 rows=512 mask=yes block=128 "
        "cost=512 [heuristic]\n"
        "    s0: HistogramAggregate\n"
        "  pass 1: grouped-scan [grouped-masked] t0 by g groups=4 "
        "sort=v0 rows=512 cost=2048 [heuristic] (rejected: segment=1024)\n"
        "    s1: CountMinAggregate\n"
        "  pass 2: fit [local] t1 rows=512 max_iters=5 tol=0.0001 "
        "cost=2560 [heuristic]\n"
        "    irls: IRLSTask"
    )


# -- measured calibration -> planner costs ------------------------------------

def _cal(engines, **kw):
    from repro.core.calibration import Calibration
    return Calibration(backend="cpu", timestamp="2026-08-07T00:00:00",
                       engines=engines, kernels=kw.get("kernels", {}),
                       grouped_block=kw.get("grouped_block", []))


def test_calibration_flips_grouped_choice_and_explain(table):
    """An active calibration whose measurements contradict the heuristic
    must drive BOTH the selection and the explain() annotation; without
    activation the PR-5 heuristic behavior is unchanged."""
    from repro.core import calibration
    cal = _cal({
        "grouped-segment": {"sketch": [
            {"rows": 512, "groups": 4, "seconds": 2.0e-3}]},
        "grouped-masked": {"sketch": [
            {"rows": 512, "groups": 4, "seconds": 5.0e-4}]},
    })
    sess = Session()
    sess.grouped_scan(_cm(), table, "g", num_groups=GROUPS,
                      columns=("item",))
    with calibration.use(cal):
        txt = sess.explain()
    assert "[grouped-masked]" in txt, txt       # measured ranking wins
    assert "[measured cpu@2026-08-07T00:00:00]" in txt
    assert "cost=0.50ms" in txt and "segment=2.00ms" in txt
    # calibration file on disk but NOT activated: heuristics, unchanged
    txt2 = sess.explain()
    assert "[grouped-segment]" in txt2 and "[heuristic]" in txt2
    assert "cost=1024" in txt2


def test_calibration_refused_on_another_backend(monkeypatch, tmp_path):
    """A calibration measured on one backend never drives another's
    plans: activation by use() and by MADJAX_CALIBRATION both raise."""
    from repro.core import calibration
    cal = _cal({})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="measured on backend 'cpu'"):
        with calibration.use(cal):
            pass
    assert calibration.current() is None
    path = calibration.save(cal, str(tmp_path / "cpu.json"))
    monkeypatch.setenv("MADJAX_CALIBRATION", path)
    with pytest.raises(ValueError, match="measured on backend 'cpu'"):
        calibration.current()


def test_calibration_partial_coverage_falls_back(table):
    """Measured seconds never rank against heuristic row counts: a
    calibration missing ANY candidate leaves the whole selection on the
    heuristic model."""
    from repro.core import calibration
    cal = _cal({"grouped-masked": {"generic": [
        {"rows": 512, "groups": 4, "seconds": 1e-6}]}})  # no segment entry
    with calibration.use(cal):
        m, costs, src = select_grouped_method(512, 4, segment_ok=True)
    assert m == "segment" and src == {"kind": "heuristic"}
    assert costs["segment"] == 1024


def test_calibration_bucket_interpolation():
    from repro.core.calibration import Calibration
    cal = Calibration(
        backend="cpu", timestamp="t", kernels={}, grouped_block=[
            {"rows": 1024, "groups": 4, "block": 256},
            {"rows": 1 << 20, "groups": 4, "block": 4096}],
        engines={"local": {"generic": [
            {"rows": 1000, "seconds": 1.0},
            {"rows": 1_000_000, "seconds": 50.0}]}})
    # nearest log2 bucket, linearly scaled in rows
    assert cal.engine_seconds("local", "generic", 2000) == 2.0
    assert cal.engine_seconds("local", "generic", 500_000) == 25.0
    # class fallback: unmeasured class uses the generic tables
    assert cal.engine_seconds("local", "xtx", 1000) == 1.0
    assert cal.engine_seconds("sharded", "generic", 1000) is None
    # measured-best grouped block per shape bucket
    assert cal.grouped_block_size(2048, 4) == 256
    assert cal.grouped_block_size(1 << 19, 4) == 4096


def test_calibration_drives_segment_block_size():
    from repro.core import calibration
    from repro.core.aggregates import segment_block_size
    heur = segment_block_size(10_000, 10)
    cal = _cal({}, grouped_block=[{"rows": 10_000, "groups": 10,
                                   "block": 512}])
    with calibration.use(cal):
        assert segment_block_size(10_000, 10) == 512
        # explicit block_size still wins over the measurement
        assert segment_block_size(10_000, 10, 64) == 64
    assert segment_block_size(10_000, 10) == heur
