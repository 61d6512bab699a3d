"""MADlib method library behaviour tests (Table 1 + Table 2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table, synthetic_classification_table, \
    synthetic_regression_table


@pytest.fixture(scope="module")
def keys(key):
    return jax.random.split(key, 12)


# -- linear regression (§4.1) ------------------------------------------------

def test_linregr_matches_numpy(key):
    from repro.methods.linregr import linregr
    tbl, b = synthetic_regression_table(key, 8192, 12)
    res = linregr(tbl, block_size=1024)
    x = np.asarray(tbl["x"], np.float64)
    y = np.asarray(tbl["y"], np.float64)
    ref, *_ = np.linalg.lstsq(x, y, rcond=None)
    np.testing.assert_allclose(np.asarray(res.coef), ref, rtol=1e-3, atol=1e-4)
    assert float(res.r2) > 0.99
    assert float(res.condition_no) >= 1.0
    assert np.all(np.asarray(res.p_values) <= 1.0)
    assert float(res.num_rows) == 8192


def test_linregr_sharded_equals_local(key, mesh1):
    from repro.methods.linregr import linregr
    tbl, _ = synthetic_regression_table(key, 4096, 8)
    local = linregr(tbl)
    sharded = linregr(tbl.distribute(mesh1), block_size=512)
    np.testing.assert_allclose(np.asarray(local.coef),
                               np.asarray(sharded.coef), rtol=1e-4, atol=1e-5)


# -- logistic regression (§4.2) ----------------------------------------------

def test_logregr_irls(key):
    from repro.methods.logregr import logregr
    tbl, b = synthetic_classification_table(key, 8192, 6)
    res = logregr(tbl, max_iters=25)
    assert res.converged
    assert res.n_iters < 15
    assert float(jnp.linalg.norm(res.coef - b)) < 0.3
    # Wald z-stats should flag all 6 true nonzero coefficients
    assert np.all(np.abs(np.asarray(res.z_stats)) > 2.0)


def test_logregr_sgd_agrees_with_irls(key):
    from repro.methods.logregr import logregr, logregr_sgd
    tbl, _ = synthetic_classification_table(key, 8192, 6)
    irls = logregr(tbl)
    w = logregr_sgd(tbl, epochs=10, stepsize=0.5, batch=128, key=key)
    cos = float(jnp.vdot(w, irls.coef)
                / (jnp.linalg.norm(w) * jnp.linalg.norm(irls.coef)))
    assert cos > 0.98


# -- k-means (§4.3) ----------------------------------------------------------

@pytest.fixture(scope="module")
def blobs(keys):
    centers = jnp.array([[0., 0.], [6., 6.], [0., 6.], [6., 0.]])
    assign = jax.random.randint(keys[0], (4000,), 0, 4)
    pts = centers[assign] + 0.4 * jax.random.normal(keys[1], (4000, 2))
    return Table.from_columns({"x": pts}), centers


def test_kmeans_recovers_blobs(blobs, keys):
    from repro.methods.kmeans import kmeans_fit
    tbl, centers = blobs
    res = kmeans_fit(tbl, 4, key=keys[2], max_iters=30)
    assert res.converged
    # each true center has a learned centroid within 0.5
    d = jnp.linalg.norm(res.centroids[:, None] - centers[None], axis=-1)
    assert float(jnp.max(jnp.min(d, axis=0))) < 0.5
    # SSE non-increasing across Lloyd rounds
    assert all(a >= b - 1e-3 for a, b in
               zip(res.sse_trace, res.sse_trace[1:]))


def test_kmeans_two_pass_equals_fused(blobs, keys):
    from repro.methods.kmeans import kmeans_fit
    tbl, _ = blobs
    seed = jax.random.normal(keys[3], (4, 2)) * 3.0
    a = kmeans_fit(tbl, 4, init_centroids=seed, max_iters=15,
                   variant="fused")
    b = kmeans_fit(tbl, 4, init_centroids=seed, max_iters=15,
                   variant="two_pass")
    np.testing.assert_allclose(np.asarray(a.centroids),
                               np.asarray(b.centroids), rtol=1e-4, atol=1e-4)


# -- naive bayes / svm / decision tree ---------------------------------------

@pytest.fixture(scope="module")
def two_class(keys):
    x0 = jax.random.normal(keys[4], (2000, 4)) + 1.5
    x1 = jax.random.normal(keys[5], (2000, 4)) - 1.5
    x = jnp.concatenate([x0, x1])
    y = jnp.concatenate([jnp.zeros(2000), jnp.ones(2000)])
    return Table.from_columns({"x": x, "y": y})


def test_naive_bayes(two_class):
    from repro.methods.naive_bayes import naive_bayes_fit, naive_bayes_predict
    model = naive_bayes_fit(two_class, 2, block_size=512)
    acc = float(jnp.mean(
        naive_bayes_predict(model, two_class["x"])
        == two_class["y"].astype(jnp.int32)))
    assert acc > 0.97
    np.testing.assert_allclose(np.asarray(model.mean[0]), 1.5, atol=0.2)
    np.testing.assert_allclose(np.asarray(model.mean[1]), -1.5, atol=0.2)


def test_svm(two_class, key):
    from repro.methods.svm import svm_fit, svm_predict
    w = svm_fit(two_class, epochs=5, stepsize=0.1, key=key)
    acc = float(jnp.mean(svm_predict(w, two_class["x"])
                         == two_class["y"].astype(jnp.int32)))
    assert acc > 0.97


def test_decision_tree_xor(keys):
    from repro.methods.decision_tree import decision_tree_fit, \
        decision_tree_predict
    x = jax.random.uniform(keys[6], (4000, 3))
    y = ((x[:, 0] > 0.5) ^ (x[:, 1] > 0.3)).astype(jnp.int32)
    tbl = Table.from_columns({"x": x, "y": y})
    tree = decision_tree_fit(tbl, num_classes=2, max_depth=3)
    acc = float(jnp.mean(decision_tree_predict(tree, x) == y))
    assert acc > 0.95  # xor needs depth 2 — checks real splits happen


# -- SVD / low-rank ----------------------------------------------------------

def test_svd_power_decaying_spectrum(keys):
    from repro.methods.svd import svd_power
    u = jnp.linalg.qr(jax.random.normal(keys[7], (512, 16)))[0]
    v = jnp.linalg.qr(jax.random.normal(keys[8], (16, 16)))[0]
    s_true = jnp.array([100., 50., 25., 12.] + [1.0] * 12)
    a = (u * s_true) @ v.T
    tbl = Table.from_columns({"a": a})
    s, vecs = svd_power(tbl, 4, n_iters=30, key=keys[9])
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_true[:4]),
                               rtol=1e-2)


def test_lowrank_sgd_learns(keys):
    from repro.methods.svd import lowrank_sgd
    nr, nc, rank = 64, 48, 3
    L0 = jax.random.normal(keys[10], (nr, rank))
    R0 = jax.random.normal(keys[11], (nc, rank))
    ii = jax.random.randint(keys[0], (6000,), 0, nr)
    jj = jax.random.randint(keys[1], (6000,), 0, nc)
    vv = jnp.sum(L0[ii] * R0[jj], -1)
    tbl = Table.from_columns({"i": ii.astype(jnp.float32),
                              "j": jj.astype(jnp.float32), "v": vv})
    params = lowrank_sgd(tbl, nr, nc, rank, key=keys[2])
    pred = jnp.sum(params["L"][ii] * params["R"][jj], -1)
    rmse = float(jnp.sqrt(jnp.mean((pred - vv) ** 2)))
    assert rmse < 0.5 * float(jnp.std(vv))


# -- LDA / association rules -------------------------------------------------

def test_lda_perplexity_decreases(keys):
    from repro.methods.lda import lda_fit
    V, K = 40, 3
    topics = jax.random.dirichlet(keys[3], jnp.full((V,), 0.05), (K,))
    docs = []
    for d in range(150):
        kd = jax.random.fold_in(keys[4], d)
        th = jax.random.dirichlet(kd, jnp.full((K,), 0.3))
        docs.append(jax.random.multinomial(jax.random.fold_in(kd, 1), 80,
                                       th @ topics))
    tbl = Table.from_columns({"counts": jnp.stack(docs)})
    learned, trace = lda_fit(tbl, K, V, max_iters=10, key=keys[5])
    assert trace[-1] < 0.6 * trace[0]
    np.testing.assert_allclose(np.asarray(jnp.sum(learned, -1)), 1.0,
                               rtol=1e-4)


def test_apriori_finds_planted_rule():
    from repro.methods.assoc_rules import apriori
    rng = np.random.default_rng(0)
    items = (rng.random((2000, 8)) < 0.15).astype(np.float32)
    items[:, 1] = np.maximum(items[:, 0], items[:, 1])
    tbl = Table.from_columns({"items": jnp.asarray(items)})
    res = apriori(tbl, min_support=0.05, min_confidence=0.6, max_len=2)
    assert any(r[0] == (0,) and r[1] == (1,) for r in res.rules)
    # support monotonicity: subsets at least as frequent
    for s, supp in res.supports.items():
        if len(s) == 2:
            assert supp <= res.supports[(s[0],)] + 1e-9
            assert supp <= res.supports[(s[1],)] + 1e-9


# -- sketches / quantiles ----------------------------------------------------

def test_countmin_overestimates_within_bound(key):
    from repro.methods.sketches import countmin_sketch, countmin_query
    items = jax.random.randint(key, (20000,), 0, 500)
    tbl = Table.from_columns({"item": items})
    sk = countmin_sketch(tbl, depth=4, width=2048, block_size=4096)
    est = np.asarray(countmin_query(sk, jnp.arange(500)))
    true = np.bincount(np.asarray(items), minlength=500)
    assert np.all(est >= true)                    # CM never underestimates
    assert np.mean(est - true) < 2 * 20000 / 2048  # ~2n/w error bound


def test_fm_distinct_count(key):
    from repro.methods.sketches import fm_distinct_count
    for true_n in (100, 500, 2000):
        items = jax.random.randint(key, (30000,), 0, true_n)
        tbl = Table.from_columns({"item": items})
        est = float(fm_distinct_count(tbl, block_size=8192))
        assert 0.4 * true_n < est < 2.5 * true_n


def test_quantiles_gaussian(key):
    from repro.methods.quantiles import quantiles
    tbl = Table.from_columns({"v": jax.random.normal(key, (50000,))})
    qs = np.asarray(quantiles(tbl, [0.1, 0.5, 0.9], block_size=8192))
    np.testing.assert_allclose(qs, [-1.2816, 0.0, 1.2816], atol=0.05)


# -- sparse vectors / array ops ----------------------------------------------

def test_rle_roundtrip_and_dots():
    from repro.methods.sparse_vector import (rle_decode, rle_dot_dense,
                                             rle_dot_rle, rle_encode)
    dense = jnp.asarray(
        np.repeat([0., 2., 0., 5., 0.], [100, 20, 50, 10, 60])
        .astype(np.float32))
    other = jnp.asarray(
        np.repeat([1., 0., 3.], [80, 100, 60]).astype(np.float32))
    v = rle_encode(dense, 16)
    w = rle_encode(other, 16)
    assert int(v.n_runs) == 5
    np.testing.assert_array_equal(np.asarray(rle_decode(v)),
                                  np.asarray(dense))
    ref = float(dense @ other)
    assert abs(float(rle_dot_dense(v, other)) - ref) < 1e-3
    assert abs(float(rle_dot_rle(v, w)) - ref) < 1e-3


def test_closest_column():
    from repro.methods.array_ops import closest_column
    m = jnp.array([[0., 0.], [10., 10.], [5., 0.]])
    idx, dist = closest_column(m, jnp.array([4.4, 0.2]))
    assert int(idx) == 2
    np.testing.assert_allclose(float(dist), np.hypot(0.6, 0.2), rtol=1e-5)


# -- Table-2 SGD registry ----------------------------------------------------

def test_sgd_registry_all_models_run(key):
    from repro.methods.sgd_models import REGISTRY, fit_sgd_model
    tbl, b = synthetic_regression_table(key, 2048, 6)
    for name in ("least_squares", "lasso"):
        w = fit_sgd_model(name, tbl, jnp.zeros(6), epochs=3, stepsize=0.05,
                          key=key)
        assert float(jnp.linalg.norm(w - b)) < 0.8
    assert set(REGISTRY) == {"least_squares", "lasso", "logistic", "svm",
                             "recommendation", "crf"}
