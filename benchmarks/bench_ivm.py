"""Incremental view maintenance — delta-fold refresh vs full rescan.

The always-fresh-dashboard workload: a retained statement batch
(profile + count-min + FM + linregr) over an append-only fact table.
Without IVM every read after an ingest batch pays a full rescan;
:class:`~repro.core.materialize.MaterializedHandle` pays only the fold
of the NEW rows plus one merge per member (§4.1 merge combinators).
This bench appends ``fraction`` of the base rows and times both paths
on the SAME grown table with warm compile caches, so the ratio is pure
data-pass work:

* **update** — restore the handle's prefix pin, then ``result()``:
  slice + delta fold of the appended rows + merge + final.
* **rescan** — un-pin the handle entirely (stale epoch), then
  ``result()``: full fold of all rows + final.

Columns are dyadic f32 in ``[0, 1)`` (multiples of 1/8), so every
fold sum stays exactly representable and the bench can ASSERT the
tentpole's exactness claim: the delta-merged state is bit-identical to
the rescanned state, leaf for leaf.  A grouped section does the same
for a per-group linregr (fixed ``num_groups``).

``run()`` feeds the CSV harness (benchmarks/run.py); ``python -m
benchmarks.bench_ivm [--json out.json]`` emits the JSON document for
the bench trajectory and the CI smoke artifact.
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np

from repro.core import (
    ProfileAggregate, Table, materialize, trace_execution,
)
from repro.core.plan import GroupedScanAgg, ScanAgg
from repro.methods.linregr import LinregrAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate


def _dyadic(rng, shape):
    """f32 multiples of 1/8 in [0, 1): sums/sums-of-squares over a few
    hundred thousand rows stay under 2**24 when scaled, i.e. exact."""
    return (rng.integers(0, 8, shape).astype(np.float32) / 8.0)


def _columns(rows: int, dims: int, groups: int, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    return {"x": _dyadic(rng, (rows, dims)),
            "y": _dyadic(rng, (rows,)),
            "item": rng.integers(0, 1000, rows).astype(np.int32),
            "g": rng.integers(0, groups, rows).astype(np.int32)}


def _nodes(table: Table, block_size: int) -> list:
    return [
        ScanAgg(ProfileAggregate(), table, columns=("x", "y"),
                block_size=block_size),
        ScanAgg(CountMinAggregate(4, 1024, item_col="item"), table,
                columns=("item",), block_size=block_size),
        ScanAgg(FMAggregate(item_col="item"), table, columns=("item",),
                block_size=block_size),
        ScanAgg(LinregrAggregate(), table, columns={"x": "x", "y": "y"},
                block_size=block_size),
    ]


def _bit_identical(s1, s2) -> bool:
    l1, l2 = jax.tree.leaves(s1), jax.tree.leaves(s2)
    return len(l1) == len(l2) and all(
        np.asarray(a).tobytes() == np.asarray(b).tobytes()
        for a, b in zip(l1, l2))


def _pin_of(h) -> tuple:
    return (h._state, h._version, h._epoch, h._n_rows)


def _restore(h, pin) -> None:
    # Reset the handle to a saved pin so the SAME refresh path can be
    # timed repeatedly (refresh() consumes the staleness otherwise).
    h._state, h._version, h._epoch, h._n_rows = pin
    h._result_cache = None


def _time_refresh(h, pin, reps: int) -> tuple[float, int]:
    """(min seconds over reps, delta events per refresh) for
    restore-pin -> result(), blocking on every result leaf."""
    best = float("inf")
    deltas = 0
    for _ in range(reps):
        _restore(h, pin)
        with trace_execution() as t:
            t0 = time.perf_counter()
            out = h.result()
            for leaf in jax.tree.leaves(out):
                jax.block_until_ready(leaf)
            best = min(best, time.perf_counter() - t0)
        deltas = len(t.deltas)
    return best, deltas


def _section(handle_factory, base_rows: int, delta_cols: dict,
             reps: int) -> dict:
    """Time update vs rescan for one handle shape over one append."""
    h = handle_factory()
    h.result()                       # warm: full build + final programs
    prefix_pin = _pin_of(h)
    h.table.append(delta_cols)
    h.result()                       # warm: delta fold + merge programs
    delta_state = h._state
    up_s, up_deltas = _time_refresh(h, prefix_pin, reps)

    # stale-epoch pin => refresh() takes the full-rescan path
    rescan_pin = (prefix_pin[0], -1, -1, prefix_pin[3])
    _restore(h, rescan_pin)
    h.result()                       # warm (build program already cached)
    rescan_state = h._state
    re_s, _ = _time_refresh(h, rescan_pin, reps)
    return {
        "base_rows": base_rows,
        "delta_rows": int(next(iter(delta_cols.values())).shape[0]),
        "update_seconds": up_s, "update_deltas": up_deltas,
        "rescan_seconds": re_s,
        "speedup": re_s / up_s,
        "bit_identical": _bit_identical(delta_state, rescan_state),
    }


def bench(rows: int = 200_000, dims: int = 8, groups: int = 16,
          reps: int = 3, block_size: int = 4096,
          fractions=(0.01, 0.05, 0.10)) -> dict:
    out: dict = {"config": {"rows": rows, "dims": dims, "groups": groups,
                            "reps": reps, "block_size": block_size,
                            "fractions": list(fractions)},
                 "fractions": {}}
    for f in fractions:
        m = max(int(rows * f), 1)
        table = Table.from_columns(_columns(rows, dims, groups, seed=0))
        delta = _columns(m, dims, groups, seed=1)
        sec = _section(lambda: materialize(_nodes(table, block_size)),
                       rows, delta, reps)
        out["fractions"][f"{f:g}"] = sec

    # grouped living view: per-group linregr, fixed group count
    table = Table.from_columns(_columns(rows, dims, groups, seed=0))
    delta = _columns(max(int(rows * 0.05), 1), dims, groups, seed=1)
    out["grouped"] = _section(
        lambda: materialize(GroupedScanAgg(
            LinregrAggregate(), table, "g", num_groups=groups,
            columns={"x": "x", "y": "y"}, block_size=block_size)),
        rows, delta, reps)

    headline = out["fractions"].get("0.05") or next(
        iter(out["fractions"].values()))
    out["speedup"] = headline["speedup"]
    out["bit_identical"] = (
        all(s["bit_identical"] for s in out["fractions"].values())
        and out["grouped"]["bit_identical"])
    return out


def run(rows: int = 200_000, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(rows=rows, reps=reps)
    h = r["fractions"].get("0.05") or next(iter(r["fractions"].values()))
    return [
        ("ivm_update_5pct", h["update_seconds"] * 1e6,
         f"deltas={h['update_deltas']}"),
        ("ivm_rescan_5pct", h["rescan_seconds"] * 1e6, ""),
        ("ivm_speedup_5pct", h["speedup"],
         f"bit_identical={r['bit_identical']}"),
        ("ivm_grouped_speedup_5pct", r["grouped"]["speedup"],
         f"bit_identical={r['grouped']['bit_identical']}"),
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
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block-size", type=int, default=4096)
    args = ap.parse_args()
    doc = bench(rows=args.rows, dims=args.dims, groups=args.groups,
                reps=args.reps, block_size=args.block_size)
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
