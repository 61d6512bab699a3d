"""Partitioned grouped-scan core: GroupedView layout, segment-vs-masked
equivalence, grouped one-pass oracle tests, and skewed-convergence
compaction.

The refactor contract mirrors PR 2's: changing HOW GROUP BY executes
(partitioned segments vs per-group masks) changes cost, never results.
Integer-state aggregates (sketches, histograms) and exactly-representable
(dyadic) float data make the grouped-vs-solo oracle checks bit-identical;
everything else is held to f32-ulp-level tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    IterativeTask, ProfileAggregate, Table, fit_grouped, fit_stream,
    run_grouped, run_local, run_stream,
)
from repro.core.aggregates import Aggregate, MERGE_SUM
from repro.methods.linregr import (
    LinregrAggregate, linregr, linregr_grouped,
)
from repro.methods.naive_bayes import naive_bayes_fit, naive_bayes_grouped
from repro.methods.quantiles import quantiles, quantiles_grouped
from repro.methods.sketches import (
    countmin_sketch, countmin_sketch_grouped, fm_distinct_count,
    fm_distinct_count_grouped,
)


def _dyadic(key, shape):
    """Small multiples of 1/8: f32 sums of their pairwise products are
    exact, so any fold order gives bit-identical aggregate states."""
    return jnp.round(jax.random.normal(key, shape) * 8.0) / 8.0


@pytest.fixture(scope="module")
def grouped_table(key):
    n, d, G = 1200, 4, 4
    kx, ky, kg, ki, kv = jax.random.split(key, 5)
    return Table.from_columns({
        "x": _dyadic(kx, (n, d)),
        "y": jax.random.randint(ky, (n,), 0, 3).astype(jnp.float32),
        "g": jax.random.randint(kg, (n,), 0, G),
        "item": jax.random.randint(ki, (n,), 0, 300),
        "v": jax.random.normal(kv, (n,)),
    }), G


# -- GroupedView layout -------------------------------------------------------

def test_grouped_view_layout(key):
    g = jax.random.randint(key, (500,), 0, 7)
    tbl = Table.from_columns({"v": jnp.arange(500.0), "g": g})
    view = tbl.group_by("g")
    gn = np.asarray(g)
    assert view.num_groups == 7
    np.testing.assert_array_equal(np.asarray(view.gids), np.sort(gn))
    np.testing.assert_array_equal(np.asarray(view.counts),
                                  np.bincount(gn, minlength=7))
    offs = np.asarray(view.offsets)
    for i in range(7):
        seg = np.asarray(view.table["v"])[offs[i]:offs[i + 1]]
        np.testing.assert_array_equal(np.sort(seg),
                                      np.sort(np.arange(500.0)[gn == i]))
    # stable sort: within a group, original row order is preserved
    np.testing.assert_array_equal(
        np.asarray(view.perm), np.argsort(gn, kind="stable"))


def test_grouped_view_aligned_blocks(key):
    g = jax.random.randint(key, (300,), 0, 5)
    tbl = Table.from_columns({"v": jnp.arange(300.0), "g": g})
    view = tbl.group_by("g", 6)  # group 5 empty
    cols, valid, bgids = view.aligned_blocks(64)
    counts = np.asarray(view.counts)
    assert bgids.shape[0] == int((-(-counts // 64)).sum())
    # every block holds rows of exactly one group, padding masked out
    vg = np.asarray(view.gids)
    offs = np.asarray(view.offsets)
    vals = np.asarray(cols["v"]).reshape(-1, 64)
    vm = np.asarray(valid).reshape(-1, 64)
    for j, gid in enumerate(np.asarray(bgids)):
        rows = vals[j][vm[j]]
        src = np.asarray(view.table["v"])[offs[gid]:offs[gid + 1]]
        assert np.all(np.isin(rows, src))
    assert int(np.asarray(valid).sum()) == 300


# -- segment vs masked equivalence on random layouts --------------------------

@pytest.mark.parametrize("seed,G,bs", [(0, 3, None), (1, 8, 64), (2, 16, 17)])
def test_run_grouped_segment_matches_masked(seed, G, bs):
    """The two grouped strategies agree on random group layouts (empty
    groups, non-contiguous ids, ragged sizes included)."""
    k = jax.random.PRNGKey(seed)
    kx, kg = jax.random.split(k)
    n = 700
    # leave some ids unused so empty groups are exercised
    g = jax.random.randint(kg, (n,), 0, max(1, G - 2))
    tbl = Table.from_columns({
        "x": jax.random.normal(kx, (n, 3)),
        "v": jax.random.normal(jax.random.fold_in(k, 3), (n,)),
        "g": g,
    })
    seg = run_grouped(ProfileAggregate(), tbl, "g", G, method="segment",
                      block_size=bs)
    msk = run_grouped(ProfileAggregate(), tbl, "g", G, method="masked",
                      block_size=bs)
    for col in ("x", "v"):
        for stat in ("count", "sum", "sumsq", "min", "max", "mean", "std"):
            np.testing.assert_allclose(
                np.asarray(seg[col][stat]), np.asarray(msk[col][stat]),
                rtol=1e-5, atol=1e-5, err_msg=f"{col}.{stat}")


def test_run_grouped_mask_filters_rows(key):
    """run_grouped accepts a base mask like run_local, on both paths."""
    n = 400
    g = jax.random.randint(key, (n,), 0, 4)
    tbl = Table.from_columns({"v": jnp.arange(n, dtype=jnp.float32),
                              "g": g})
    mask = jnp.arange(n) % 2 == 0
    for method in ("segment", "masked"):
        out = run_grouped(ProfileAggregate(), tbl, "g", 4, mask=mask,
                          method=method)
        counts = np.asarray(out["v"]["count"])
        expect = np.bincount(np.asarray(g)[np.asarray(mask)], minlength=4)
        np.testing.assert_array_equal(counts, expect, err_msg=method)


def test_run_grouped_generic_merge_falls_back():
    """A generic-merge aggregate cannot take the segment path: auto falls
    back to masked, and forcing segment raises."""
    from repro.methods.kmeans import GumbelPickAggregate
    n = 128
    tbl = Table.from_columns({
        "x": jnp.ones((n, 2)), "d2": jnp.ones((n,)),
        "__row__": jnp.arange(n, dtype=jnp.int32),
        "g": (jnp.arange(n) % 2).astype(jnp.int32),
    })
    agg = GumbelPickAggregate(jax.random.PRNGKey(0), 2)
    out = run_grouped(agg, tbl, "g", 2)  # auto -> masked, must not raise
    assert np.asarray(out["score"]).shape == (2,)
    with pytest.raises(ValueError, match="segment"):
        run_grouped(agg, tbl, "g", 2, method="segment")


def test_run_grouped_accepts_prebuilt_view(key):
    """A GroupedView pays the sort once and is accepted in place of a
    Table by both strategies, with identical results."""
    n = 600
    g = jax.random.randint(key, (n,), 0, 5)
    tbl = Table.from_columns({
        "v": jax.random.normal(jax.random.fold_in(key, 1), (n,)), "g": g})
    vw = tbl.group_by("g", 5)
    for method in ("segment", "masked"):
        from_view = run_grouped(ProfileAggregate(), vw, method=method)
        from_tbl = run_grouped(ProfileAggregate(), tbl, "g", 5,
                               method=method)
        for stat in ("count", "sum", "min", "max"):
            np.testing.assert_allclose(
                np.asarray(from_view["v"][stat]),
                np.asarray(from_tbl["v"][stat]), rtol=1e-6, atol=1e-6,
                err_msg=f"{method}.{stat}")
    with pytest.raises(ValueError, match="group_col"):
        run_grouped(ProfileAggregate(), tbl)  # Table without a key column
    with pytest.raises(ValueError, match="disagrees"):
        run_grouped(ProfileAggregate(), vw, num_groups=9)


def test_run_grouped_blocked_fold_used(key):
    """The masked path now honors block_size (regression: it used to fold
    the whole table in one unblocked transition)."""
    calls = []

    class Counting(ProfileAggregate):
        def transition(self, state, block, mask):
            calls.append(block["v"].shape[0])
            return super().transition(state, block, mask)

    n = 256
    tbl = Table.from_columns({"v": jnp.arange(n, dtype=jnp.float32),
                              "g": jnp.zeros((n,), jnp.int32)})
    run_grouped(Counting(), tbl, "g", 1, method="masked", block_size=64)
    assert calls and all(b == 64 for b in calls)


# -- grouped one-pass oracle tests (bit-identical) ----------------------------

def test_naive_bayes_grouped_matches_solo(grouped_table):
    """Dyadic features make every sufficient-statistic sum exact in f32,
    so the grouped model is BIT-IDENTICAL to fitting each group alone."""
    tbl, G = grouped_table
    nb = naive_bayes_grouped(tbl, "g", 3)
    assert nb.mean.shape == (G, 3, 4)
    gv = np.asarray(tbl["g"])
    for i in range(G):
        sel = gv == i
        solo = naive_bayes_fit(Table.from_columns(
            {"x": tbl["x"][sel], "y": tbl["y"][sel]}), 3)
        np.testing.assert_array_equal(np.asarray(nb.log_prior[i]),
                                      np.asarray(solo.log_prior))
        np.testing.assert_array_equal(np.asarray(nb.mean[i]),
                                      np.asarray(solo.mean))
        np.testing.assert_array_equal(np.asarray(nb.var[i]),
                                      np.asarray(solo.var))


def test_quantiles_grouped_matches_solo(grouped_table):
    """Histogram counts are integers and each group's range comes from its
    own (exact) min/max, so per-group quantiles are BIT-IDENTICAL to the
    solo two-pass sketch on that group's rows."""
    tbl, G = grouped_table
    qs = [0.1, 0.25, 0.5, 0.9]
    qg = quantiles_grouped(tbl, "g", qs, bins=512)
    assert qg.shape == (G, len(qs))
    gv = np.asarray(tbl["g"])
    for i in range(G):
        solo = quantiles(Table.from_columns({"v": tbl["v"][gv == i]}), qs,
                         bins=512)
        np.testing.assert_array_equal(np.asarray(qg[i]), np.asarray(solo))


def test_sketches_grouped_match_solo(grouped_table):
    """Integer sketch states are order-independent: grouped Count-Min and
    FM are BIT-IDENTICAL to sketching each group alone."""
    tbl, G = grouped_table
    cm = countmin_sketch_grouped(tbl, "g", depth=4, width=256)
    fm = fm_distinct_count_grouped(tbl, "g", num_hashes=4, bits=16)
    assert cm.shape == (G, 4, 256) and fm.shape == (G,)
    gv = np.asarray(tbl["g"])
    for i in range(G):
        st = Table.from_columns({"item": tbl["item"][gv == i]})
        np.testing.assert_array_equal(
            np.asarray(cm[i]),
            np.asarray(countmin_sketch(st, depth=4, width=256)))
        np.testing.assert_array_equal(
            np.asarray(fm[i]),
            np.asarray(fm_distinct_count(st, num_hashes=4, bits=16)))


def test_linregr_grouped_bit_identical_on_dyadic_data(key):
    """With exactly-representable data the partitioned fold's raw state
    equals the solo fold's bitwise, and so do the coefficients solved
    from it."""
    n, d, G = 1024, 4, 4
    kx, kb, kg, ke = jax.random.split(key, 4)
    x = _dyadic(kx, (n, d))
    b = _dyadic(kb, (d,))
    y = jnp.round((x @ b + 0.1 * jax.random.normal(ke, (n,))) * 8) / 8
    g = jax.random.randint(kg, (n,), 0, G)
    tbl = Table.from_columns({"x": x, "y": y, "g": g})
    lr = linregr_grouped(tbl, "g")
    states = run_grouped(LinregrAggregate(), tbl, "g", finalize=False)
    gv = np.asarray(g)
    for i in range(G):
        sel = gv == i
        solo_tbl = Table.from_columns({"x": x[sel], "y": y[sel]})
        solo = linregr(solo_tbl)
        solo_state = run_local(LinregrAggregate(), solo_tbl, finalize=False)
        for leaf in solo_state:
            np.testing.assert_array_equal(np.asarray(states[leaf][i]),
                                          np.asarray(solo_state[leaf]))
        np.testing.assert_array_equal(np.asarray(lr.coef[i]),
                                      np.asarray(solo.coef))
        # r2 is finalized through a different jit program per path, which
        # may round its last float differently (~1 ulp)
        np.testing.assert_allclose(np.asarray(lr.r2[i]),
                                   np.asarray(solo.r2), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(lr.num_rows[i]),
                                      np.asarray(solo.num_rows))
        # Wald statistics go through a BATCHED eigh under the grouped
        # vmap, whose pseudo-inverse differs from the solo one by ~1 ulp.
        np.testing.assert_allclose(np.asarray(lr.std_err[i]),
                                   np.asarray(solo.std_err), rtol=1e-5)


# -- fit_grouped: layouts, compaction, skewed convergence ---------------------

class _MeanAggregate(Aggregate):
    merge_ops = MERGE_SUM

    def init(self, block):
        return {"s": jnp.zeros(()), "n": jnp.zeros(())}

    def transition(self, state, block, mask):
        m = mask.astype(jnp.float32)
        return {"s": state["s"] + jnp.sum(block["k"] * m),
                "n": state["n"] + jnp.sum(m)}

    def final(self, s):
        return s["s"] / jnp.maximum(s["n"], 1.0)


class _CountdownTask(IterativeTask):
    """Deterministic convergence schedule: group g's metric is
    ``mean(k) - rounds_done``, so it converges after ceil(mean(k)) rounds
    — the controlled skewed-convergence workload."""

    def init_state(self, columns):
        return {"it": jnp.zeros(())}

    def make_aggregate(self, state):
        return _MeanAggregate()

    def update(self, state, out):
        return {"it": state["it"] + 1.0}

    def metric(self, prev, new, out):
        return out - new["it"]


def _skewed_table(n=6000, G=6):
    sizes = [(i + 1) * n // ((G * (G + 1)) // 2) for i in range(G)]
    sizes[-1] += n - sum(sizes)
    g = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                         for i, s in enumerate(sizes)])
    k = (g + 1).astype(jnp.float32)  # group i converges after i+1 rounds
    return Table.from_columns({"k": k, "g": g}), sizes


def test_fit_grouped_skewed_convergence_compacts():
    """As groups freeze, the segment layout's per-round pass shrinks: the
    active-row trace decreases monotonically and the total blocks scanned
    stay below rounds x full-table blocks."""
    tbl, sizes = _skewed_table()
    res = fit_grouped(_CountdownTask(), tbl, "g", max_iters=20, tol=0.5,
                      block_size=128)
    G = len(sizes)
    np.testing.assert_array_equal(res.n_iters, np.arange(1, G + 1))
    assert res.stats["layout"] == "segment"
    ar = res.stats["active_rows"]
    assert len(ar) == G
    assert all(ar[i] > ar[i + 1] for i in range(G - 1)), ar
    assert res.stats["blocks"] < res.stats["blocks_full_scan"]
    # round r scans exactly the rows of groups that still iterate
    expect = [sum(sizes[r:]) for r in range(G)]
    np.testing.assert_array_equal(ar, expect)


def test_fit_grouped_layouts_agree():
    """layout='segment' and layout='masked' produce the same models and
    per-group iteration counts."""
    tbl, _ = _skewed_table(n=2000, G=4)
    seg = fit_grouped(_CountdownTask(), tbl, "g", max_iters=10, tol=0.5,
                      layout="segment")
    msk = fit_grouped(_CountdownTask(), tbl, "g", max_iters=10, tol=0.5,
                      layout="masked")
    assert msk.stats["layout"] == "masked"
    np.testing.assert_array_equal(seg.n_iters, msk.n_iters)
    np.testing.assert_array_equal(np.asarray(seg.converged),
                                  np.asarray(msk.converged))
    np.testing.assert_allclose(np.asarray(seg.state["it"]),
                               np.asarray(msk.state["it"]))


def test_fit_grouped_multi_statement_task_falls_back(key):
    """Tasks overriding iteration() (two-pass k-means style) cannot use the
    segment layout; auto routes them to masked."""

    class TwoScan(_CountdownTask):
        def iteration(self, state, run_pass):
            out = run_pass(self.make_aggregate(state))
            out = 0.5 * (out + run_pass(self.make_aggregate(state)))
            new = self.update(state, out)
            return new, out, self.metric(state, new, out)

    tbl, _ = _skewed_table(n=1000, G=3)
    res = fit_grouped(TwoScan(), tbl, "g", max_iters=10, tol=0.5)
    assert res.stats["layout"] == "masked"
    np.testing.assert_array_equal(res.n_iters, [1, 2, 3])
    # forcing the segment layout must refuse, not silently skip the
    # override's second scan
    with pytest.raises(ValueError, match="single-scan"):
        fit_grouped(TwoScan(), tbl, "g", max_iters=10, tol=0.5,
                    layout="segment")


# -- streaming guards (regression: bare StopIteration) ------------------------

def test_run_stream_empty_raises():
    with pytest.raises(ValueError, match="empty block stream"):
        run_stream(ProfileAggregate(), iter([]))


def test_fit_stream_empty_factory_raises():
    with pytest.raises(ValueError, match="no blocks"):
        fit_stream(_CountdownTask(), lambda: iter([]), max_iters=3)
