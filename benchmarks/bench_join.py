"""Star-schema joined GROUP BY: shared-sort sort-merge vs gather-materialize.

The classical plan for ``SELECT dim.attr, agg(...) FROM fact JOIN dim ON
fact.fk = dim.key GROUP BY dim.attr`` materializes the join — gathers
the dimension attribute onto every fact row — and then groups the
widened table, paying the dimension sort AND the fact partitioning sort
once per statement.  The join layer (``core/join.py`` +
``JoinedGroupedScanAgg``) instead resolves keys device-side against the
memoized dimension key sort and routes ONE fact-aligned int32 gid column
into the unchanged grouped core, so an N-statement batch over the same
star triple pays 2 sorts TOTAL (dim keys + fact partition) and one
fused pass.

Sections (sorts/scans counted by :func:`repro.core.trace_execution`,
results checked BIT-identical to a numpy-lookup materialized oracle):

* **naive** — per statement: fresh tables (no shared memo, the
  pre-join-layer cost), device gather of the dimension attribute onto
  fact rows, own partitioning sort, own scan.
* **planned** — the same statements as one ``Session`` batch of
  ``JoinedGroupedScanAgg`` nodes: one key resolution, one shared sort
  pair, ONE fused pass.

``run()`` feeds the CSV harness (benchmarks/run.py); ``python -m
benchmarks.bench_join [--json out.json]`` emits the JSON document the
CI smoke asserts on (bit_identical, per-statement sort counts, the
fused explain).
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    Join, JoinedGroupedScanAgg, ProfileAggregate, Session, Table, execute,
    run_grouped, trace_execution,
)
from repro.core.plan import GroupedScanAgg
from repro.methods.linregr import LinregrAggregate
from repro.methods.quantiles import HistogramAggregate


def _star_columns(fact_rows: int, dim_rows: int, groups: int,
                  dims: int) -> tuple[dict, dict]:
    rng = np.random.default_rng(0)
    # sparse, shuffled dimension keys: keys are not row positions
    dim_keys = rng.permutation(dim_rows * 13)[:dim_rows].astype(np.int32)
    dim_attr = (rng.permutation(dim_rows) % groups).astype(np.int32)
    fk = dim_keys[rng.integers(0, dim_rows, fact_rows)].astype(np.int32)
    x = rng.standard_normal((fact_rows, dims), dtype=np.float32)
    b = rng.standard_normal(dims, dtype=np.float32)
    y = (x @ b + 0.1 * rng.standard_normal(fact_rows, dtype=np.float32))
    fact = {"x": x, "y": y.astype(np.float32), "fk": fk,
            "item": rng.integers(0, 1000, fact_rows).astype(np.int32)}
    dim = {"key": dim_keys, "region": dim_attr}
    return fact, dim


def _aggs():
    """The 3-statement joined batch: scan-dominated (cheap-transition)
    statistics per dimension attribute, all over the same star triple —
    the regime where the per-statement sorts ARE the cost the shared-sort
    plan removes."""
    return [
        ("linregr_joined", lambda: LinregrAggregate(),
         {"x": "x", "y": "y"}),
        ("profile_joined", lambda: ProfileAggregate(), ("y",)),
        ("hist_joined",
         lambda: HistogramAggregate(-8.0, 8.0, 1024, "y"), ("y",)),
    ]


def _time(fn, reps: int) -> tuple[float, int, int]:
    """(min seconds over reps, scans, sorts) after one untimed warmup,
    blocking on every result leaf."""
    fn()
    best = float("inf")
    scans = sorts = 0
    for _ in range(reps):
        with trace_execution() as t:
            t0 = time.perf_counter()
            out = fn()
            for leaf in jax.tree.leaves(out):
                jax.block_until_ready(leaf)
            best = min(best, time.perf_counter() - t0)
        scans, sorts = len(t.scans), len(t.sorts)
    return best, scans, sorts


def bench(fact_rows: int = 200_000, dim_rows: int = 512,
          groups: int = 64, dims: int = 8, reps: int = 3) -> dict:
    fact_cols, dim_cols = _star_columns(fact_rows, dim_rows, groups, dims)
    n_stmts = len(_aggs())
    out: dict = {"config": {"fact_rows": fact_rows, "dim_rows": dim_rows,
                            "groups": groups, "dims": dims, "reps": reps,
                            "statements": n_stmts}}

    # Prepared statements (bench_plan's "prepared" regime): aggregate
    # instances are built ONCE so engine program caches hit on every rep
    # and the timings compare the two join strategies' DATA work —
    # sorts, gathers, key resolution, passes — not trace/compile.
    prepared = [(name, make(), proj) for name, make, proj in _aggs()]

    # -- naive: gather-materialize, fresh tables per statement ------------
    def naive():
        res = []
        for name, agg, proj in prepared:
            f = Table.from_columns(fact_cols)   # fresh: no shared memos
            d = Table.from_columns(dim_cols)
            sorted_keys, perm = d.sort_permutation("key")  # dim sort
            pos = jnp.clip(jnp.searchsorted(sorted_keys, f["fk"]),
                           0, dim_rows - 1)
            gid = d["region"][perm][pos]        # gather attr onto fact
            tbl = f.with_column("g", gid.astype(jnp.int32))
            res.append(execute(GroupedScanAgg(
                agg, tbl, "g", groups, columns=proj, label=name)))
        return res

    # -- planned: one joined batch over one star triple -------------------
    fact = Table.from_columns(fact_cols)
    dim = Table.from_columns(dim_cols)
    stmts = [JoinedGroupedScanAgg(
        agg, Join(fact, dim, "fk", "key", "region"), groups,
        columns=proj, label=name) for name, agg, proj in prepared]

    def planned():
        sess = Session()
        for node in stmts:
            sess.statement(node)
        return sess.run()

    def planned_cold():
        # memoized sort/resolution products would hide the planned
        # path's real per-batch cost: drop them so every timed rep pays
        # its own key resolution + shared sort pair, mirroring naive's
        # fresh-tables-per-statement accounting
        fact.invalidate(), dim.invalidate()
        return planned()

    n_s, n_scans, n_sorts = _time(naive, reps)
    p_s, p_scans, p_sorts = _time(planned_cold, reps)
    out["naive"] = {"seconds": n_s, "scans": n_scans, "sorts": n_sorts,
                    "sorts_per_stmt": n_sorts / n_stmts}
    out["planned"] = {"seconds": p_s, "scans": p_scans, "sorts": p_sorts,
                      "sorts_per_stmt": p_sorts / n_stmts}
    out["speedup"] = n_s / p_s

    # -- bit-identity vs the materialized oracle --------------------------
    lookup = {int(k): int(a) for k, a in zip(dim_cols["key"],
                                             dim_cols["region"])}
    gids = np.array([lookup[int(f)] for f in fact_cols["fk"]], np.int32)
    got = planned()
    identical = True
    for (name, make, proj), g in zip(_aggs(), got):
        # the oracle sees exactly the statement's projection, so
        # schema-driven aggregates (profile) produce matching trees
        names = proj.values() if isinstance(proj, dict) else proj
        oracle_tbl = Table.from_columns(
            {**{c: fact_cols[c] for c in names}, "g": gids})
        want = run_grouped(make(), oracle_tbl, "g", groups)
        a_l, b_l = jax.tree.leaves(g), jax.tree.leaves(want)
        identical &= len(a_l) == len(b_l) and all(
            bool(jnp.all(jnp.asarray(a) == jnp.asarray(b)))
            for a, b in zip(a_l, b_l))
    out["bit_identical"] = identical

    sess = Session()
    for node in stmts:
        sess.statement(node)
    out["explain"] = sess.explain()
    return out


def run(fact_rows: int = 200_000, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(fact_rows=fact_rows, reps=reps)
    return [
        ("join_naive_3stmt", r["naive"]["seconds"] * 1e6,
         f"sorts={r['naive']['sorts']} scans={r['naive']['scans']}"),
        ("join_planned_3stmt", r["planned"]["seconds"] * 1e6,
         f"sorts={r['planned']['sorts']} scans={r['planned']['scans']}"),
        ("join_speedup", r["speedup"],
         f"bit_identical={r['bit_identical']} sorts/stmt "
         f"{r['naive']['sorts_per_stmt']:.2f}->"
         f"{r['planned']['sorts_per_stmt']:.2f}"),
    ]


if __name__ == "__main__":
    import argparse

    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON document here (default: stdout)")
    ap.add_argument("--fact-rows", type=int, default=200_000)
    ap.add_argument("--dim-rows", type=int, default=512)
    ap.add_argument("--groups", type=int, default=64)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    doc = bench(fact_rows=args.fact_rows, dim_rows=args.dim_rows,
                groups=args.groups, dims=args.dims, reps=args.reps)
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
