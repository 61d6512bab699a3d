"""GROUP BY execution cost: the partitioned grouped-scan core vs the
masked-vmap lowering.

  * ``run_grouped`` on a skewed-G workload — the segment path folds all
    groups in one O(n) blocked scan of group-aligned blocks; the masked
    path scans the full table once per group (O(G·n)).  The speedup
    should track G.
  * ``fit_grouped`` under skewed convergence — groups converge at
    spread-out rounds; the segment layout gather-compacts still-active
    groups' blocks each round, so iters/sec stays high as the tail
    thins, while the masked layout pays G full scans every round.

  * ``--sharded`` — device-count scaling of the SHARDED grouped engine:
    ``run_grouped(mesh=)`` / ``fit_grouped(mesh=)`` on meshes of 1, 2,
    4, ... devices (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a CPU
    smoke).  Emits per-device-count rows_per_sec / iters_per_sec JSON.

  * ``--kernel={auto,ref,pallas}`` — which segment-fold implementation
    the linregr transitions route through (``use_kernel=`` on the
    aggregate): the registry-dispatched jnp ref, the Pallas kernel
    (interpret mode off-TPU — correctness path, not a throughput
    number), or auto.  The JSON records the RESOLVED kernel name from
    the execution trace plus blocks/sec of the segment scan.

``run()`` feeds the CSV harness (benchmarks/run.py); ``python -m
benchmarks.bench_grouped [--json out.json]`` emits a JSON document for
the bench trajectory and the CI smoke artifact.
"""

from __future__ import annotations

import json
import time
import warnings

import jax
import jax.numpy as jnp

from repro.core import Table, fit_grouped, run_grouped, trace_execution
from repro.core.aggregates import segment_block_size
from repro.methods.linregr import LinregrAggregate
from repro.methods.logregr import IRLSTask


def _skewed_groups(key, rows: int, groups: int) -> jax.Array:
    """Zipf-ish group sizes: a few big segments, a long tail of small
    ones (the shape that makes O(G·n) masking hurt most)."""
    w = 1.0 / (jnp.arange(groups) + 1.0)
    probs = w / jnp.sum(w)
    return jax.random.choice(key, groups, (rows,), p=probs).astype(jnp.int32)


def _grouped_table(key, rows: int, dims: int, groups: int) -> Table:
    kx, kb, kg, ke = jax.random.split(key, 4)
    x = jax.random.normal(kx, (rows, dims))
    b = jax.random.normal(kb, (dims,))
    y = x @ b + 0.1 * jax.random.normal(ke, (rows,))
    return Table.from_columns({"x": x, "y": y,
                               "g": _skewed_groups(kg, rows, groups)})


def _skewed_logistic_table(key, rows: int, dims: int, groups: int) -> Table:
    """Skewed sizes AND skewed convergence: per-group coefficient scales
    spread the IRLS iteration counts, so group models freeze at very
    different rounds — the gather-compaction showcase."""
    kx, kb, kg, ku = jax.random.split(key, 4)
    x = jax.random.normal(kx, (rows, dims))
    g = _skewed_groups(kg, rows, groups)
    b = jax.random.normal(kb, (groups, dims)) \
        * (1.0 + (jnp.arange(groups)[:, None] % 7))
    p = jax.nn.sigmoid(jnp.sum(x * b[g], -1))
    y = (jax.random.uniform(ku, (rows,)) < p).astype(jnp.float32)
    return Table.from_columns({"x": x, "y": y, "g": g})


def _time(fn, reps: int) -> float:
    """Min wall-clock over reps, after one untimed call.  run_grouped /
    fit_grouped build a fresh jitted closure per call, so every rep pays
    the same trace+dispatch overhead on BOTH strategies — the comparison
    is apples-to-apples; the partitioning sort is hoisted out by passing
    a prebuilt GroupedView where the strategy uses one."""
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree.leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return best


def bench(rows: int = 200_000, dims: int = 8, groups: int = 64,
          fit_groups: int = 64, max_iters: int = 25, reps: int = 3,
          kernel: str = "auto") -> dict:
    key = jax.random.PRNGKey(0)
    out: dict = {"config": {"rows": rows, "dims": dims, "groups": groups,
                            "fit_groups": fit_groups,
                            "max_iters": max_iters, "reps": reps,
                            "kernel": kernel}}

    # --- one-pass: run_grouped linregr states, segment vs masked ---------
    tbl = _grouped_table(key, rows, dims, groups)
    view = tbl.group_by("g", groups)  # sort paid once, outside the timer
    agg = LinregrAggregate(use_kernel=kernel)
    one_pass = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # forced-pallas interpret note
        for method in ("segment", "masked"):
            s = _time(lambda m=method: run_grouped(agg, view, method=m),
                      reps)
            one_pass[method] = {"seconds": s,
                                "rows_per_sec": rows / s}
        # resolved kernel + blocks/sec of the segment scan, from the trace
        with trace_execution() as t:
            run_grouped(agg, view, method="segment")
    bs = segment_block_size(rows, groups)
    nb = int(view.aligned_blocks(bs)[2].shape[0])
    ev = t.kernels[0] if t.kernels else None
    one_pass["kernel"] = {
        "requested": kernel,
        "resolved": None if ev is None else ev.engine,
        "name": None if ev is None else ev.detail["name"],
        "blocks": nb,
        "blocks_per_sec": nb / one_pass["segment"]["seconds"],
    }
    one_pass["segment_speedup"] = \
        one_pass["masked"]["seconds"] / one_pass["segment"]["seconds"]
    out["run_grouped"] = one_pass

    # --- iterative: fit_grouped IRLS under skewed convergence ------------
    ftbl = _skewed_logistic_table(jax.random.fold_in(key, 1), rows, dims,
                                  fit_groups)
    fit_stats = {}
    rounds = {}
    for layout in ("segment", "masked"):
        def one(la=layout):
            return fit_grouped(IRLSTask(), ftbl, "g", fit_groups,
                               max_iters=max_iters, tol=1e-6, layout=la)
        res = one()  # compile + capture diagnostics
        t0 = time.perf_counter()
        res = one()
        s = time.perf_counter() - t0
        rounds[layout] = int(res.n_iters.max())
        fit_stats[layout] = {"seconds": s,
                             "iters_per_sec": rounds[layout] / s}
        if res.stats["layout"] == "segment":
            fit_stats[layout]["blocks"] = res.stats["blocks"]
            fit_stats[layout]["blocks_full_scan"] = \
                res.stats["blocks_full_scan"]
            fit_stats[layout]["n_iters_min_max"] = \
                [int(res.n_iters.min()), int(res.n_iters.max())]
    fit_stats["segment_speedup"] = \
        fit_stats["masked"]["seconds"] / fit_stats["segment"]["seconds"]
    out["fit_grouped"] = fit_stats

    # --- iters/sec vs G (segment layout scaling) -------------------------
    sweep = []
    for g_sweep in (max(2, fit_groups // 4), fit_groups, 4 * fit_groups):
        t = _skewed_logistic_table(jax.random.fold_in(key, g_sweep), rows,
                                   dims, g_sweep)

        def one_sweep(tt=t, gg=g_sweep):
            return fit_grouped(IRLSTask(), tt, "g", gg,
                               max_iters=max_iters, tol=1e-6,
                               layout="segment")
        r = one_sweep()
        t0 = time.perf_counter()
        r = one_sweep()
        s = time.perf_counter() - t0
        sweep.append({"groups": g_sweep, "seconds": s,
                      "iters_per_sec": int(r.n_iters.max()) / s})
    out["fit_grouped_vs_G"] = sweep
    return out


def bench_sharded(rows: int = 200_000, dims: int = 8, groups: int = 64,
                  fit_groups: int = 64, max_iters: int = 25,
                  reps: int = 3) -> dict:
    """Device-count scaling of the sharded grouped engine.

    For each mesh size (1, 2, 4, ... up to the available device count):
    one ``run_grouped`` segment scan and one ``fit_grouped`` IRLS fit,
    both with the group-aligned blocks chunked across the mesh.  The
    local (mesh=None) engine is the 0-device baseline row.

    Each ``run_grouped(mesh=)`` call re-places the block layout on the
    mesh, so ``seconds`` includes that host-side gather + device_put;
    ``placement_seconds`` reports it separately (measured via
    ``GroupedView.sharded_blocks``) so the scan-only scaling is
    ``seconds - placement_seconds``.  ``fit_grouped`` amortizes one
    placement over the whole multi-round fit.
    """
    from repro.core.compat import make_mesh

    key = jax.random.PRNGKey(0)
    tbl = _grouped_table(key, rows, dims, groups)
    view = tbl.group_by("g", groups)
    ftbl = _skewed_logistic_table(jax.random.fold_in(key, 1), rows, dims,
                                  fit_groups)
    agg = LinregrAggregate()
    devices = jax.devices()
    counts = [c for c in (1, 2, 4, 8, 16, 32) if c <= len(devices)]
    out: dict = {"config": {"rows": rows, "dims": dims, "groups": groups,
                            "fit_groups": fit_groups,
                            "max_iters": max_iters, "reps": reps,
                            "available_devices": len(devices)},
                 "device_scaling": []}

    def one_point(mesh, label):
        s = _time(lambda: run_grouped(agg, view, method="segment",
                                      mesh=mesh), reps)
        from repro.core.aggregates import segment_block_size
        bs = segment_block_size(rows, groups)
        place = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = view.sharded_blocks(mesh, ("data",), bs) if mesh is not \
                None else view.aligned_blocks(bs)
            jax.block_until_ready(jax.tree.leaves(out[0])[0])
            place = min(place, time.perf_counter() - t0)
        def fit_once():
            return fit_grouped(IRLSTask(), ftbl, "g", fit_groups,
                               max_iters=max_iters, tol=1e-6,
                               layout="segment", mesh=mesh)
        res = fit_once()  # compile + diagnostics, untimed
        fs = float("inf")
        for _ in range(reps):  # honor --reps like the one-pass points
            t0 = time.perf_counter()
            fit_once()
            fs = min(fs, time.perf_counter() - t0)
        rounds = int(res.n_iters.max())
        return {"devices": label,
                "run_grouped": {"seconds": s, "rows_per_sec": rows / s,
                                "placement_seconds": place},
                "fit_grouped": {"seconds": fs,
                                "iters_per_sec": rounds / fs,
                                "rounds": rounds,
                                "blocks": res.stats["blocks"]}}

    base = one_point(None, 0)  # local engine baseline
    out["device_scaling"].append(base)
    for nd in counts:
        mesh = make_mesh((nd,), ("data",), devices=devices[:nd])
        point = one_point(mesh, nd)
        point["run_grouped"]["speedup_vs_local"] = \
            base["run_grouped"]["seconds"] / point["run_grouped"]["seconds"]
        point["fit_grouped"]["speedup_vs_local"] = \
            base["fit_grouped"]["seconds"] / point["fit_grouped"]["seconds"]
        out["device_scaling"].append(point)
    return out


def run(rows: int = 200_000, groups: int = 64, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(rows=rows, groups=groups, reps=reps)
    res = []
    for method in ("segment", "masked"):
        e = r["run_grouped"][method]
        res.append((f"run_grouped_{method}", e["seconds"] * 1e6,
                    f"rows_per_sec={e['rows_per_sec']:.0f}"))
    res.append(("run_grouped_segment_speedup",
                r["run_grouped"]["segment_speedup"], ""))
    for layout in ("segment", "masked"):
        e = r["fit_grouped"][layout]
        res.append((f"fit_grouped_{layout}", e["seconds"] * 1e6,
                    f"iters_per_sec={e['iters_per_sec']:.2f}"))
    return res


if __name__ == "__main__":
    import argparse

    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON document here (default: stdout)")
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--fit-groups", type=int, default=64)
    ap.add_argument("--iters", type=int, default=25)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--kernel", choices=("auto", "ref", "pallas"),
                    default="auto",
                    help="segment-fold implementation for the one-pass "
                         "linregr scan (pallas runs interpret off-TPU)")
    ap.add_argument("--sharded", action="store_true",
                    help="device-count scaling of the sharded grouped "
                         "engine instead of the segment-vs-masked bench")
    args = ap.parse_args()
    if args.sharded:
        doc = bench_sharded(rows=args.rows, groups=args.groups,
                            fit_groups=args.fit_groups,
                            max_iters=args.iters, reps=args.reps)
    else:
        doc = bench(rows=args.rows, groups=args.groups,
                    fit_groups=args.fit_groups, max_iters=args.iters,
                    reps=args.reps, kernel=args.kernel)
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
