"""Figure 4/5 reproduction: linear-regression UDA execution times.

The paper's claims:
  (1) runtime O(k^3 + n·k^2/p) in #variables k, rows n, segments p;
  (2) near-perfect linear speedup in p (6->24 segments);
  (3) v0.1 (nested-loop outer product) vs v0.3 (blocked rank-update)
      version history (§4.4).

This container exposes one CPU core, so p-speedup is reproduced under the
shared-nothing model the paper itself relies on: each segment folds its
n/p rows independently (associative merge — the property tested in
test_properties.py), so cluster time = single-segment time over n/p rows
+ a k×k merge.  We measure exactly that per-segment fold and report the
implied speedup, alongside the directly-measured k-sweep and the
v0.1-vs-v0.3 comparison which need no parallelism.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import Table, run_local, synthetic_regression_table
from repro.methods.linregr import LinregrAggregate


def _timeit(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps, out


def naive_outer_product_xtx(x, y):
    """v0.1alpha: row-at-a-time rank-1 updates (lax.fori over rows)."""
    n, d = x.shape

    def body(i, acc):
        xtx, xty = acc
        xi = x[i]
        return xtx + jnp.outer(xi, xi), xty + xi * y[i]

    return jax.lax.fori_loop(
        0, n, body, (jnp.zeros((d, d)), jnp.zeros((d,))))


def run(rows: int = 200_000, reps: int = 3):
    key = jax.random.PRNGKey(0)
    results = []

    # --- (1) k-sweep: the paper's #variables column (Fig 4) -------------
    agg = LinregrAggregate()
    for k in (10, 20, 40, 80, 160, 320):
        tbl, _ = synthetic_regression_table(key, rows, k)
        fn = jax.jit(lambda cols: agg.final(agg.transition(
            agg.init(cols), cols, jnp.ones((rows,), bool))))
        dt, _ = _timeit(fn, dict(tbl.columns), reps=reps)
        results.append((f"linregr_k{k}_n{rows}", dt * 1e6,
                        f"rows_per_s={rows / dt:.3g}"))

    # --- (2) implied p-speedup: per-segment fold of n/p rows ------------
    k = 80
    base_dt = None
    for p in (1, 6, 12, 18, 24):
        n_seg = rows // p
        tbl, _ = synthetic_regression_table(key, n_seg, k)
        fn = jax.jit(lambda cols, m: agg.transition(agg.init(cols), cols, m))
        dt, _ = _timeit(fn, dict(tbl.columns),
                        jnp.ones((n_seg,), bool), reps=reps)
        if p == 1:
            base_dt = dt
        speedup = base_dt / dt
        results.append((f"linregr_seg{p}_k{k}", dt * 1e6,
                        f"implied_speedup={speedup:.2f}x_of_{p}x"))

    # --- (3) §4.4 version history: v0.1 loop vs v0.3 blocked ------------
    n_small = 20_000
    for k in (10, 40, 80):
        tbl, _ = synthetic_regression_table(key, n_small, k)
        x, y = tbl["x"], tbl["y"]
        v01 = jax.jit(naive_outer_product_xtx)
        dt01, _ = _timeit(v01, x, y, reps=1)
        v03 = jax.jit(lambda x, y: (x.T @ x, x.T @ y))
        dt03, _ = _timeit(v03, x, y, reps=reps)
        results.append((f"linregr_v01_loop_k{k}", dt01 * 1e6, ""))
        results.append((f"linregr_v03_blocked_k{k}", dt03 * 1e6,
                        f"speedup_over_v01={dt01 / dt03:.1f}x"))
    return results


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for name, us, extra in run():
        print(f"{name},{us:.1f},{extra}")
