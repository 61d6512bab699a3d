"""§4.2/§4.3 reproduction: multipass iteration costs, under the unified
iterative executor.

  * logregr IRLS and k-means (fused Lloyd) per-iteration cost and
    iterations/sec, local vs sharded engine — the executor's compiled
    ``lax.while_loop``/``scan`` fast path means the whole fit is one XLA
    program on either engine.
  * driver overhead: compiled loop vs the paper-faithful host driver
    (``mode="host"``), reproducing the paper's "driver overhead is a
    fraction of a second" claim.
  * k-means two-pass (paper-faithful, 2 scans/round) vs fused single
    pass (footnote 1: "cannot be expressed in standard SQL").

``run()`` feeds the CSV harness (benchmarks/run.py); ``python -m
benchmarks.bench_iterative [--json out.json]`` emits a JSON document for
the bench trajectory.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from repro.core import Table, synthetic_classification_table
from repro.core.compat import make_mesh
from repro.core.iterative import fit
from repro.methods.kmeans import KMeansTask, kmeans_fit
from repro.methods.logregr import IRLSTask, logregr


def _time_fit(task_factory, table, *, iters: int, reps: int,
              mode: str = "compiled") -> float:
    """Steady-state seconds per iteration of a fixed-count fit.

    ``fit()`` jits a fresh closure per call, so a naive warmup never warms
    anything and a single timing would be compile-dominated.  Instead we
    time counted fits of ``iters`` and ``2·iters`` rounds and divide the
    delta — compile time (length-independent for a rolled scan) and fixed
    setup cancel, leaving the marginal per-iteration cost."""
    def run_n(n: int) -> float:
        t0 = time.perf_counter()
        res = fit(task_factory(), table, max_iters=n, tol=None, mode=mode)
        jax.block_until_ready(jax.tree.leaves(res.state)[0])
        return time.perf_counter() - t0
    run_n(iters)
    run_n(2 * iters)  # warm persistent caches / autotuning
    delta = 0.0
    for _ in range(reps):
        t1 = run_n(iters)
        t2 = run_n(2 * iters)
        delta += t2 - t1
    return max(delta / (reps * iters), 1e-9)


def bench(rows: int = 100_000, k_vars: int = 20, k_clusters: int = 8,
          dims: int = 16, iters: int = 10, reps: int = 3) -> dict:
    key = jax.random.PRNGKey(0)
    out: dict = {"config": {"rows": rows, "k_vars": k_vars,
                            "k_clusters": k_clusters, "dims": dims,
                            "iters": iters, "reps": reps,
                            "n_devices": jax.device_count()}}

    mesh = make_mesh((jax.device_count(),), ("data",))

    # --- logregr IRLS ----------------------------------------------------
    tbl, _ = synthetic_classification_table(key, rows, k_vars)
    engines = {"local": tbl, "sharded": tbl.distribute(mesh)}
    out["logregr_irls"] = {}
    for name, t in engines.items():
        s = _time_fit(IRLSTask, t, iters=iters, reps=reps)
        out["logregr_irls"][name] = {"per_iter_s": s, "iters_per_sec": 1 / s}
    s_host = _time_fit(IRLSTask, tbl, iters=iters, reps=reps, mode="host")
    out["logregr_irls"]["host_mode"] = {"per_iter_s": s_host,
                                        "iters_per_sec": 1 / s_host}
    out["logregr_irls"]["driver_overhead_s"] = max(
        s_host - out["logregr_irls"]["local"]["per_iter_s"], 0.0)
    res = logregr(tbl, max_iters=30)
    out["logregr_irls"]["iters_to_converge"] = res.n_iters

    # --- k-means ---------------------------------------------------------
    kk = jax.random.split(key, 3)
    centers = jax.random.normal(kk[0], (k_clusters, dims)) * 4
    pts = centers[jax.random.randint(kk[1], (rows,), 0, k_clusters)] \
        + jax.random.normal(kk[2], (rows, dims))
    tblk = Table.from_columns({"x": pts})
    seed_c = jax.random.normal(kk[0], (k_clusters, dims)) * 2
    out["kmeans"] = {}
    for name, t in (("local", tblk), ("sharded", tblk.distribute(mesh))):
        s = _time_fit(lambda: KMeansTask(seed_c), t, iters=iters, reps=reps)
        out["kmeans"][name] = {"per_iter_s": s, "iters_per_sec": 1 / s}
    for variant in ("fused", "two_pass"):
        t0 = time.perf_counter()
        r = kmeans_fit(tblk, k_clusters, init_centroids=seed_c,
                       max_iters=iters, variant=variant)
        dt = (time.perf_counter() - t0) / r.n_iters
        out["kmeans"][f"{variant}_fit_per_iter_s"] = dt
    return out


def run(rows: int = 100_000, k_vars: int = 20, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(rows=rows, k_vars=k_vars, reps=reps)
    res = []
    for method in ("logregr_irls", "kmeans"):
        for eng in ("local", "sharded"):
            e = r[method][eng]
            res.append((f"{method}_{eng}_per_iter", e["per_iter_s"] * 1e6,
                        f"iters_per_sec={e['iters_per_sec']:.1f}"))
    res.append(("logregr_driver_overhead",
                r["logregr_irls"]["driver_overhead_s"] * 1e6,
                f"iters={r['logregr_irls']['iters_to_converge']}"))
    for variant in ("fused", "two_pass"):
        res.append((f"kmeans_{variant}_per_iter",
                    r["kmeans"][f"{variant}_fit_per_iter_s"] * 1e6, ""))
    return res


if __name__ == "__main__":
    import argparse

    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON document here (default: stdout)")
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    doc = bench(rows=args.rows, iters=args.iters, reps=args.reps)
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
