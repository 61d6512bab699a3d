"""Table 2 reproduction (§5.1): six models, ONE convex-optimization
abstraction, one SGD solver.  Reports fit time + final objective per row
of the table — the Wisconsin claim is that adding a model costs only its
objective definition ("a matter of days" -> here, lines of code)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import Table, synthetic_classification_table, \
    synthetic_regression_table
from repro.core.convex import sgd
from repro.methods.crf import crf_init_params, crf_program, \
    extract_features
from repro.methods.sgd_models import (lasso_program, least_squares_program)
from repro.methods.logregr import logistic_program
from repro.methods.svm import svm_program
from repro.methods.svd import lowrank_program


def _obj(prog, params, columns, n):
    mask = jnp.ones((n,), bool)
    return float(prog.total_loss(params, columns, mask)) / n


def run(rows: int = 20_000, d: int = 16, epochs: int = 3):
    key = jax.random.PRNGKey(0)
    results = []
    reg_tbl, _ = synthetic_regression_table(key, rows, d)
    cls_tbl, _ = synthetic_classification_table(key, rows, d)

    jobs = [
        ("least_squares", least_squares_program(), reg_tbl,
         jnp.zeros((d,)), 0.05),
        ("lasso", lasso_program(mu=0.05), reg_tbl, jnp.zeros((d,)), 0.05),
        ("logistic", logistic_program(), cls_tbl, jnp.zeros((d,)), 0.3),
        ("svm", svm_program(mu=1e-3), cls_tbl, jnp.zeros((d,)), 0.1),
    ]
    # recommendation: sparse ratings
    kk = jax.random.split(key, 4)
    nr, nc, rank = 128, 96, 4
    ii = jax.random.randint(kk[0], (rows,), 0, nr).astype(jnp.float32)
    jj = jax.random.randint(kk[1], (rows,), 0, nc).astype(jnp.float32)
    l0 = jax.random.normal(kk[2], (nr, rank))
    r0 = jax.random.normal(kk[3], (nc, rank))
    vv = jnp.sum(l0[ii.astype(int)] * r0[jj.astype(int)], -1)
    rec_tbl = Table.from_columns({"i": ii, "j": jj, "v": vv})
    rec_params = {"L": 0.5 * jax.random.normal(kk[0], (nr, rank)),
                  "R": 0.5 * jax.random.normal(kk[1], (nc, rank))}
    jobs.append(("recommendation", lowrank_program(nr, nc, rank, mu=1e-5),
                 rec_tbl, rec_params, 0.1))
    # CRF labeling
    B, T, L, F = 256, 12, 3, 64
    toks = jax.random.randint(kk[2], (B, T), 0, 30)
    feats = extract_features(toks, F)
    crf_tbl = Table.from_columns({
        "feats": feats, "labels": (toks % L).astype(jnp.int32),
        "mask": jnp.ones((B, T), jnp.float32)})
    jobs.append(("crf", crf_program(F, L, mu=1e-4), crf_tbl,
                 crf_init_params(F, L, kk[3]), 0.3))

    for name, prog, tbl, params0, lr in jobs:
        n = tbl.n_rows
        mask = jnp.ones((n,), bool)
        before = _obj(prog, params0, dict(tbl.columns), n)
        t0 = time.perf_counter()
        params = sgd(prog, tbl, params0, stepsize=lr, epochs=epochs,
                     batch=min(256, n), key=key, anneal=False)
        jax.block_until_ready(jax.tree.leaves(params)[0])
        dt = time.perf_counter() - t0
        after = _obj(prog, params, dict(tbl.columns), n)
        results.append((f"sgd_{name}", dt * 1e6,
                        f"obj {before:.4g}->{after:.4g}"))
    return results


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for name, us, extra in run():
        print(f"{name},{us:.1f},{extra}")
