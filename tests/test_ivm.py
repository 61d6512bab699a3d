"""Incremental view maintenance: versioning, delta folds, cache staleness.

The contract under test (``core/materialize.py`` + ``Table`` versioning):

* ``Table.append`` bumps ``version`` but not ``epoch``; ``invalidate``
  bumps both.  Every cache stamped with a version observes staleness.
* A retained :class:`MaterializedHandle` refreshed after an append
  folds ONLY the new rows (``kind="delta"`` in the trace, no scan) and
  the merged state is **bit-identical** to a full rescan for
  exact-state aggregates — integer sketches, dyadic-f32 sums.
* The ``group_by`` memo is version-aware: grouped refreshes re-sort
  only the delta (trace sort sizes prove it), and plan-time group
  resolution never reads an outdated view.

Plus regression tests for the two confirmed Table-layer bugs this PR
fixes (empty-view sentinel blocks; sharding of derived columns).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from repro.core import (
    GroupedScanAgg, ScanAgg, Session, Table, execute, materialize,
    run_grouped, trace_execution,
)
from repro.methods.linregr import LinregrAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate
from repro.core.templates import ProfileAggregate
from repro.distributed.sharding import row_pspec

from strategies import Draw, cases, group_layout


def _dyadic_table(draw: Draw, n: int, d: int = 3, groups: int = 4,
                  pattern=None):
    gids, _ = group_layout(draw, n, groups, pattern)
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


def _allclose(a, b) -> bool:
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(fa) == len(fb) and all(
        np.allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                    equal_nan=True)
        for x, y in zip(fa, fb))


# ---------------------------------------------------------------------------
# Table versioning + append
# ---------------------------------------------------------------------------

class TestVersioning:
    def test_append_bumps_version_not_epoch(self):
        t = Table.from_columns({"a": np.arange(4.0)})
        assert (t.version, t.epoch) == (0, 0)
        t.append({"a": np.arange(2.0)})
        assert (t.version, t.epoch) == (1, 0)
        assert t.n_rows == 6
        np.testing.assert_array_equal(np.asarray(t["a"]),
                                      [0, 1, 2, 3, 0, 1])

    def test_invalidate_bumps_version_and_epoch(self):
        t = Table.from_columns({"a": np.arange(4.0)})
        t.invalidate()
        assert (t.version, t.epoch) == (1, 1)

    def test_append_schema_errors(self):
        t = Table.from_columns({"a": np.arange(4.0),
                                "b": np.zeros((4, 2), np.float32)})
        with pytest.raises(ValueError, match="columns"):
            t.append({"a": np.arange(2.0)})
        with pytest.raises(ValueError, match="dtype"):
            t.append({"a": np.arange(2), "b": np.zeros((2, 2), np.float32)})
        with pytest.raises(ValueError, match="trailing shape"):
            t.append({"a": np.arange(2.0),
                      "b": np.zeros((2, 3), np.float32)})
        assert t.version == 0  # failed appends leave the table untouched

    def test_append_distributed_replaces_rows(self, mesh1):
        t = Table.from_columns({"a": np.arange(8.0)}).distribute(mesh1)
        t.append({"a": np.arange(4.0)})
        assert t.n_rows == 12
        assert isinstance(t["a"].sharding, NamedSharding)

    def test_group_by_memo_is_version_aware(self):
        t = Table.from_columns({"g": np.array([0, 1, 0, 1], np.int32),
                                "v": np.arange(4.0)})
        with trace_execution() as tr:
            v1 = t.group_by("g", 2)
            assert t.group_by("g", 2) is v1          # memo hit
            assert t.cached_group_by("g", 2) is v1
        assert len(tr.sorts) == 1
        t.append({"g": np.array([1], np.int32), "v": np.array([9.0])})
        assert t.cached_group_by("g", 2) is None     # stale, not served
        with trace_execution() as tr:
            v2 = t.group_by("g", 2)
        assert v2 is not v1 and len(tr.sorts) == 1
        assert v2.n_rows == 5

    def test_invalidate_clears_memo(self):
        t = Table.from_columns({"g": np.array([0, 1], np.int32)})
        t.group_by("g", 2)
        t.invalidate()
        assert t.cached_group_by("g", 2) is None
        assert not t._gb_cache


# ---------------------------------------------------------------------------
# Confirmed bug 1: empty-view sentinel blocks
# ---------------------------------------------------------------------------

class TestEmptyViewBlocks:
    def test_aligned_blocks_empty_view_pads_sentinels(self):
        t = Table.from_columns({"g": np.full(8, -1, np.int32),
                                "v": np.arange(8.0)})
        view = t.group_by("g", 3)
        cols, valid, bgids = view.aligned_blocks(4, pad_blocks_to=2)
        assert bgids.shape == (2,)                 # was (0,) before the fix
        np.testing.assert_array_equal(np.asarray(bgids), [3, 3])  # sentinel
        assert valid.shape == (8,) and not bool(valid.any())
        assert cols["v"].shape == (8,)

    def test_aligned_blocks_empty_view_no_pad_keeps_zero_blocks(self):
        t = Table.from_columns({"g": np.full(4, 9, np.int32),
                                "v": np.arange(4.0)})
        view = t.group_by("g", 2)
        cols, valid, bgids = view.aligned_blocks(4)
        assert bgids.shape == (0,) and valid.shape == (0,)

    def test_run_grouped_sharded_empty_view(self, mesh1):
        """The regression the sentinel layout protects: a sharded grouped
        pass over an all-out-of-range view must return init-state
        results for every group."""
        t = Table.from_columns({
            "g": np.full(8, -1, np.int32),
            "x": np.ones((8, 2), np.float32),
            "y": np.ones(8, np.float32),
        })
        view = t.group_by("g", 3)
        out = run_grouped(LinregrAggregate(), view, mesh=mesh1,
                          block_size=4)
        assert np.asarray(out.num_rows).shape == (3,)
        np.testing.assert_array_equal(np.asarray(out.num_rows), np.zeros(3))


# ---------------------------------------------------------------------------
# Confirmed bug 2: derived columns keep the table's sharding
# ---------------------------------------------------------------------------

class TestShardingInvariants:
    def _assert_row_sharded(self, arr):
        assert isinstance(arr.sharding, NamedSharding)
        assert arr.sharding.spec == row_pspec(("data",), arr.ndim)

    def test_with_column_distributes_new_column(self, mesh1):
        t = Table.from_columns({"a": np.arange(8.0)}).distribute(mesh1)
        t2 = t.with_column("b", jnp.arange(8.0))
        self._assert_row_sharded(t2["b"])          # was SingleDeviceSharding

    def test_map_rows_distributes_outputs(self, mesh1):
        t = Table.from_columns({"a": np.arange(8.0)}).distribute(mesh1)
        t2 = t.map_rows(lambda c: {"b": c["a"] * 2.0})
        self._assert_row_sharded(t2["b"])

    def test_pad_to_distributes_padded_columns_and_mask(self, mesh1):
        t = Table.from_columns({"a": np.arange(7.0)})
        t8, _ = t.pad_to(8)
        td = t8.distribute(mesh1)
        padded, mask = td.pad_to(16)
        self._assert_row_sharded(padded["a"])
        self._assert_row_sharded(mask)


# ---------------------------------------------------------------------------
# Materialized handles: delta folds bit-identical to rescans
# ---------------------------------------------------------------------------

class TestMaterializedScan:
    def test_delta_merge_bit_identical_seeded(self):
        for draw in cases(4, base_seed=61):
            n = draw.integers(300, 900)
            m = draw.integers(20, 150)
            t = _dyadic_table(draw, n)
            cm = CountMinAggregate(4, 64, item_col="item")
            fm = FMAggregate(4, 16, item_col="item")
            lr = LinregrAggregate()
            prof = ProfileAggregate()
            h = materialize([
                ScanAgg(cm, t, columns=("item",)),
                ScanAgg(fm, t, columns=("item",)),
                ScanAgg(lr, t, columns={"x": "x", "y": "y"}),
                ScanAgg(prof, t, columns=("x", "y")),
            ])
            h.result()
            t.append(_delta_cols(draw, m))
            with trace_execution() as tr:
                got = h.result()
            assert len(tr.deltas) == 1 and len(tr.scans) == 0, draw
            assert tr.deltas[0].detail["rows"] == m, draw
            # The IVM exactness contract: the delta-MERGED STATE is
            # bit-identical to a full rescan's state (fresh handle over
            # the grown table = pure rescan).  Finalized outputs from
            # identical states may differ by 1 ulp only because final
            # runs in a different jit program than execute's fold+final
            # — so states get bitwise asserts, results get allclose
            # (bitwise for the integer sketch, whose final is identity).
            rescan = materialize([
                ScanAgg(CountMinAggregate(4, 64, item_col="item"), t,
                        columns=("item",)),
                ScanAgg(FMAggregate(4, 16, item_col="item"), t,
                        columns=("item",)),
                ScanAgg(LinregrAggregate(), t,
                        columns={"x": "x", "y": "y"}),
                ScanAgg(ProfileAggregate(), t, columns=("x", "y")),
            ])
            assert _bitwise_equal(h._state, rescan._state), draw
            want = rescan.result()
            assert _bitwise_equal(got[0], want[0]), draw  # int counters
            for g, w in zip(got[1:], want[1:]):
                assert _allclose(g, w), draw

    def test_refresh_is_noop_at_pinned_version(self):
        draw = Draw(7)
        t = _dyadic_table(draw, 200)
        h = materialize(ScanAgg(LinregrAggregate(), t,
                                columns={"x": "x", "y": "y"}))
        h.result()
        with trace_execution() as tr:
            h.result()
        assert not tr.scans and not tr.deltas and not h.stale()

    def test_multiple_appends_chain(self):
        draw = Draw(11)
        t = _dyadic_table(draw, 256)
        h = materialize(ScanAgg(CountMinAggregate(4, 32, item_col="item"),
                                t, columns=("item",)))
        h.result()
        for _ in range(3):
            t.append(_delta_cols(draw, 64))
            h.result()
        rescan = materialize(ScanAgg(
            CountMinAggregate(4, 32, item_col="item"), t,
            columns=("item",)))
        assert _bitwise_equal(h._state, rescan._state)
        assert _bitwise_equal(h.result(), rescan.result())

    def test_invalidate_forces_rescan_and_reflects_mutation(self):
        """After an in-place mutation + invalidate(), the handle must
        not serve the retained (now wrong) state — the
        prepared-statement staleness contract."""
        t = Table.from_columns({"x": np.ones((64, 2), np.float32),
                                "y": np.ones(64, np.float32)})
        h = materialize(ScanAgg(LinregrAggregate(), t,
                                columns={"x": "x", "y": "y"}))
        before = h.result()
        t.columns["y"] = jnp.asarray(np.full(64, 2.0, np.float32))
        t.invalidate()
        with trace_execution() as tr:
            after = h.result()
        assert len(tr.scans) == 1 and len(tr.deltas) == 0
        assert not _allclose(before, after)
        want = execute(ScanAgg(LinregrAggregate(), t,
                               columns={"x": "x", "y": "y"}))
        assert _allclose(after, want)

    def test_masked_statement_rejected(self):
        t = Table.from_columns({"y": np.arange(8.0)})
        mask = jnp.ones(8, bool)
        with pytest.raises(ValueError, match="mask"):
            materialize(ScanAgg(ProfileAggregate(), t, mask=mask))

    def test_mixed_tables_rejected(self):
        t1 = Table.from_columns({"y": np.arange(8.0)})
        t2 = Table.from_columns({"y": np.arange(8.0)})
        with pytest.raises(ValueError, match="different tables"):
            materialize([ScanAgg(ProfileAggregate(), t1),
                         ScanAgg(ProfileAggregate(), t2)])


class TestMaterializedGrouped:
    def test_grouped_delta_bit_identical_and_sorts_only_delta(self):
        for draw in cases(3, base_seed=71):
            n = draw.integers(300, 800)
            m = draw.integers(16, 120)
            G = 5
            t = _dyadic_table(draw, n, groups=G)
            h = materialize(GroupedScanAgg(
                LinregrAggregate(), t, "g", num_groups=G,
                columns={"x": "x", "y": "y"}))
            h.result()
            t.append(_delta_cols(draw, m, groups=G))
            with trace_execution() as tr:
                got = h.result()
            assert len(tr.deltas) == 1 and len(tr.scans) == 0, draw
            # fresh sort only over the delta, never the full table
            assert [e.detail["n_rows"] for e in tr.sorts] == [m], draw
            rescan = materialize(GroupedScanAgg(
                LinregrAggregate(), t, "g", num_groups=G,
                columns={"x": "x", "y": "y"}))
            assert _bitwise_equal(h._state, rescan._state), draw
            assert _allclose(got, rescan.result()), draw

    def test_new_group_id_forces_rescan(self):
        draw = Draw(5)
        t = _dyadic_table(draw, 200, groups=3)
        t.columns["g"] = jnp.asarray(
            np.minimum(np.asarray(t["g"]), 2).astype(np.int32))
        h = materialize(GroupedScanAgg(
            LinregrAggregate(), t, "g", columns={"x": "x", "y": "y"}))
        assert np.asarray(h.result().num_rows).shape == (3,)
        delta = _delta_cols(draw, 32, groups=3)
        delta["g"] = np.full(32, 7, np.int32)  # a key outside pinned G
        t.append(delta)
        with trace_execution() as tr:
            got = h.result()
        assert len(tr.scans) == 1 and len(tr.deltas) == 0
        assert np.asarray(got.num_rows).shape == (8,)  # G regrew like a full run
        want = execute(GroupedScanAgg(
            LinregrAggregate(), t, "g", columns={"x": "x", "y": "y"}))
        assert _allclose(got, want)

    def test_fixed_group_count_drops_out_of_range_delta_keys(self):
        draw = Draw(13)
        t = _dyadic_table(draw, 200, groups=4)
        h = materialize(GroupedScanAgg(
            LinregrAggregate(), t, "g", num_groups=4,
            columns={"x": "x", "y": "y"}))
        h.result()
        delta = _delta_cols(draw, 24, groups=4)
        delta["g"][:8] = 9  # out of range under num_groups=4: dropped
        t.append(delta)
        with trace_execution() as tr:
            got = h.result()
        assert len(tr.deltas) == 1
        rescan = materialize(GroupedScanAgg(
            LinregrAggregate(), t, "g", num_groups=4,
            columns={"x": "x", "y": "y"}))
        assert _bitwise_equal(h._state, rescan._state)
        assert _allclose(got, rescan.result())

    def test_prebuilt_view_rejected(self):
        t = Table.from_columns({"g": np.zeros(8, np.int32),
                                "y": np.arange(8.0)})
        view = t.group_by("g", 1)
        with pytest.raises(TypeError, match="GroupedView"):
            materialize(GroupedScanAgg(ProfileAggregate(), view))


# ---------------------------------------------------------------------------
# Plan-layer staleness: cost-model group resolution
# ---------------------------------------------------------------------------

class TestPlanStaleness:
    def test_resolve_groups_never_reads_outdated_view(self):
        t = Table.from_columns({"g": np.array([0, 1, 2, 3], np.int32),
                                "y": np.arange(4.0)})
        out = execute(GroupedScanAgg(ProfileAggregate(), t, "g",
                                     columns=("y",)))
        assert np.asarray(out["y"]["count"]).shape[0] == 4  # memoized G=4
        t.append({"g": np.array([9], np.int32), "y": np.array([9.0])})
        out = execute(GroupedScanAgg(ProfileAggregate(), t, "g",
                                     columns=("y",)))
        # before the accessor fix this reused the stale view's G=4
        assert np.asarray(out["y"]["count"]).shape[0] == 10


# ---------------------------------------------------------------------------
# Session front-end
# ---------------------------------------------------------------------------

class TestSessionLivingViews:
    def test_session_materialize_and_refresh(self):
        draw = Draw(3)
        t = _dyadic_table(draw, 300)
        sess = Session()
        h = sess.materialize(
            ScanAgg(CountMinAggregate(4, 32, item_col="item"), t,
                    columns=("item",)),
            ScanAgg(LinregrAggregate(), t, columns={"x": "x", "y": "y"}))
        t.append(_delta_cols(draw, 50))
        with trace_execution() as tr:
            (res,) = sess.refresh()
        assert len(tr.deltas) == 1 and len(tr.scans) == 0
        want = execute(ScanAgg(CountMinAggregate(4, 32, item_col="item"),
                               t, columns=("item",)))
        assert _bitwise_equal(res[0], want)  # identity final: exact
        assert h in sess._materialized
