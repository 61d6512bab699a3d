"""Planned vs naive N-statement batches — the logical-plan layer's win.

An analyst wanting N independent one-pass statistics from one table used
to pay N full data passes; the plan layer's scan-sharing optimizer folds
every compatible statement into ONE pass.  Three sections, all with
scans/sorts counted by :func:`repro.core.trace_execution` (engine-entry
events, not guesses):

* **out_of_core** (headline "speedup") — the 4-statement batch over a
  host-side block stream, the regime the paper's §2.1 argues from (data
  sets larger than memory: a scan means actually moving the data).
  naive re-streams all blocks once per statement (4 host→device feeds);
  planned fuses the four statements into ONE ``run_stream`` fold.
* **in_memory** — the same batch as resident-table ``ScanAgg``
  statements, both ``first_run`` (fresh statements: per-statement
  trace+compile, what a one-shot query pays) and ``prepared`` (retained
  statements: the engine program caches hit, so only execution remains —
  on an in-memory CPU table the scan term is nearly free and fusion is
  cost-neutral, which the JSON reports transparently).
* **grouped** — the sort-dedup win: N grouped statements over one key
  pay ONE partitioning sort planned vs N when each statement owns a
  fresh table.

``run()`` feeds the CSV harness (benchmarks/run.py); ``python -m
benchmarks.bench_plan [--json out.json]`` emits a JSON document for the
bench trajectory and the CI smoke artifact.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ProfileAggregate, Session, Table, execute, trace_execution,
)
from repro.core.plan import GroupedScanAgg, ScanAgg, StreamAgg
from repro.methods.linregr import LinregrAggregate
from repro.methods.naive_bayes import NaiveBayesAggregate
from repro.methods.quantiles import HistogramAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate


def _columns(rows: int, dims: int) -> dict:
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows, dims), dtype=np.float32)
    b = rng.standard_normal(dims, dtype=np.float32)
    y = (x @ b + 0.1 * rng.standard_normal(rows, dtype=np.float32))
    return {"x": x, "y": y.astype(np.float32),
            "cls": (y > 0).astype(np.float32),
            "item": rng.integers(0, 1000, rows).astype(np.int32),
            "g": (np.arange(rows) % 16).astype(np.int32)}


def _aggs():
    """The 4-statement batch: representative one-pass statistics with
    scan-dominated (cheap-transition) folds, each with its projection so
    templated members keep their schemas under fusion."""
    return [
        ("profile", ProfileAggregate(), ("x", "y")),
        ("linregr", LinregrAggregate(), {"x": "x", "y": "y"}),
        ("quantile_hist", HistogramAggregate(-8.0, 8.0, 4096, "y"), None),
        ("naive_bayes", NaiveBayesAggregate(2), {"x": "x", "y": "cls"}),
    ]


def _time(fn, reps: int) -> tuple[float, int]:
    """(min seconds over reps, scans per call) after one untimed warmup,
    blocking on EVERY result leaf."""
    fn()
    best = float("inf")
    scans = 0
    for _ in range(reps):
        with trace_execution() as t:
            t0 = time.perf_counter()
            out = fn()
            for leaf in jax.tree.leaves(out):
                jax.block_until_ready(leaf)
            best = min(best, time.perf_counter() - t0)
        scans = len(t.scans)
    return best, scans


def _section(naive, planned, reps: int) -> dict:
    n_s, n_scans = _time(naive, reps)
    p_s, p_scans = _time(planned, reps)
    return {"naive": {"seconds": n_s, "scans": n_scans},
            "planned": {"seconds": p_s, "scans": p_scans},
            "speedup": n_s / p_s}


def bench(rows: int = 200_000, dims: int = 8, reps: int = 3,
          block_size: int = 4096) -> dict:
    cols = _columns(rows, dims)
    out: dict = {"config": {"rows": rows, "dims": dims, "reps": reps,
                            "block_size": block_size,
                            "statements": len(_aggs())}}

    # -- out-of-core: the paper's §2.1 regime (headline) ------------------
    host_blocks = [{k: v[i:i + block_size] for k, v in cols.items()}
                   for i in range(0, rows, block_size)]

    def factory():
        return iter([dict(b) for b in host_blocks])

    stream_stmts = [StreamAgg(agg, None, columns=proj, label=name)
                    for name, agg, proj in _aggs()]

    def stream_naive():
        res = []
        for node in stream_stmts:
            node.blocks = factory()  # each statement re-streams the data
            res.append(execute(node))
        return res

    def stream_planned():
        src = factory()  # ONE shared stream, fused by the planner
        sess = Session()
        for node in stream_stmts:
            node.blocks = src
            sess.statement(node)
        return sess.run()

    out["out_of_core"] = _section(stream_naive, stream_planned, reps)
    out["speedup"] = out["out_of_core"]["speedup"]

    # -- in-memory: first-run (compile included) and prepared -------------
    table = Table.from_columns(cols)

    def make_stmts():
        return [ScanAgg(agg, table, columns=proj, block_size=block_size,
                        label=name) for name, agg, proj in _aggs()]

    def inmem_naive_first():
        return [execute(node) for node in make_stmts()]

    def inmem_planned_first():
        sess = Session()
        for node in make_stmts():
            sess.statement(node)
        return sess.run()

    prepared = make_stmts()

    def inmem_naive_prepared():
        return [execute(node) for node in prepared]

    def inmem_planned_prepared():
        sess = Session()
        for node in prepared:
            sess.statement(node)
        return sess.run()

    out["in_memory"] = {
        "first_run": _section(inmem_naive_first, inmem_planned_first,
                              reps),
        "prepared": _section(inmem_naive_prepared, inmem_planned_prepared,
                             reps),
    }

    sess = Session()
    for node in make_stmts():
        sess.statement(node)
    out["explain"] = sess.explain()

    # -- grouped batches: the sort-dedup win ------------------------------
    def grouped_nodes(tbl):
        return [
            GroupedScanAgg(CountMinAggregate(depth=4, width=1024,
                                             item_col="item"), tbl, "g",
                           columns=("item",), label="countmin_grouped"),
            GroupedScanAgg(FMAggregate(item_col="item"), tbl, "g",
                           columns=("item",), label="fm_grouped"),
            GroupedScanAgg(LinregrAggregate(), tbl, "g",
                           columns=("x", "y"), label="linregr_grouped"),
        ]

    def grouped_naive():
        # fresh table per statement = no shared memo: the pre-plan cost
        res = []
        for node in grouped_nodes(table):
            node.table = Table(dict(table.columns))
            res.append(execute(node))
        return res

    def grouped_planned():
        tbl = Table(dict(table.columns))
        sess = Session()
        for node in grouped_nodes(tbl):
            sess.statement(node)
        return sess.run()

    grouped = _section(grouped_naive, grouped_planned, reps)
    with trace_execution() as t:
        grouped_naive()
    grouped["naive"]["sorts"] = len(t.sorts)
    with trace_execution() as t:
        grouped_planned()
    grouped["planned"]["sorts"] = len(t.sorts)
    out["grouped"] = grouped
    return out


def run(rows: int = 200_000, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(rows=rows, reps=reps)
    return [
        ("plan_stream_naive_4stmt", r["out_of_core"]["naive"]["seconds"]
         * 1e6, f"scans={r['out_of_core']['naive']['scans']}"),
        ("plan_stream_planned_4stmt",
         r["out_of_core"]["planned"]["seconds"] * 1e6,
         f"scans={r['out_of_core']['planned']['scans']}"),
        ("plan_stream_speedup", r["speedup"], ""),
        ("plan_inmem_first_run_speedup",
         r["in_memory"]["first_run"]["speedup"], ""),
        ("plan_grouped_speedup", r["grouped"]["speedup"],
         f"sorts {r['grouped']['naive']['sorts']}->"
         f"{r['grouped']['planned']['sorts']}"),
    ]


if __name__ == "__main__":
    import argparse

    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON document here (default: stdout)")
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block-size", type=int, default=4096)
    args = ap.parse_args()
    doc = bench(rows=args.rows, dims=args.dims, reps=args.reps,
                block_size=args.block_size)
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
