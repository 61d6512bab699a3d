"""Measured cost calibration harness — fills the planner's statistics
catalog (:mod:`repro.core.calibration`).

Micro-benches every (engine x aggregate class x shape bucket) cell on
the CURRENT backend:

  * engines: ``local``, ``sharded`` (when >1 device), ``grouped-segment``
    / ``grouped-masked``, and their ``sharded-grouped-*`` variants —
    exactly the keys :func:`repro.core.plan.select_scan_engine` /
    :func:`select_grouped_method` look up;
  * aggregate classes: ``xtx`` (linregr-shaped dense normal equations)
    and ``sketch`` (integer count-min transitions); ``generic`` is the
    per-cell mean of the measured classes, the fallback bucket for
    aggregates that declare neither;
  * shape buckets: the ``--rows`` x ``--groups`` grid, nearest-bucket
    lookup in log2 space at plan time.

Each local cell also replays compiled-HLO cost analysis
(:func:`repro.launch.hlo_analysis.analyze` over the lowered fold) so the
JSON carries dot-FLOPs / bytes-accessed context next to the wall-clock
seconds — the roofline story for WHY a cell costs what it does.  The
grouped-block sweep times the segment engine across candidate block
sizes and records the measured best per bucket
(:func:`repro.core.aggregates.segment_block_size` consumes it).  On a
TPU backend the kernel tile sweep times the ``xtx`` / ``countmin``
Pallas kernels across row tiles and records the winner (the registry's
``supports`` rankers read it back through ``calibration.kernel_param``).

The output JSON changes nothing by itself — activation is explicit
(``calibration.use(path)`` / ``MADJAX_CALIBRATION=path``).

CI smoke: ``python -m benchmarks.calibrate --tiny --interpret --out
calibration_smoke.json`` — tiny buckets, plus ``--interpret`` runs every
registered Pallas kernel body in interpret mode against its jnp ref and
records the bit-identity verdicts under ``kernel_smoke``.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Table, run_grouped, run_local, run_sharded
from repro.core import calibration
from repro.core.aggregates import segment_block_size
from repro.kernels import registry as kernels
from repro.launch import compile_cache
from repro.launch.hlo_analysis import analyze
from repro.methods.linregr import LinregrAggregate
from repro.methods.sketches import CountMinAggregate

from .roofline import _fmt_s

# aggregate class -> (factory, columns builder)
_DIMS = 8


def _xtx_cols(rng, rows):
    return {"x": jnp.asarray(rng.standard_normal((rows, _DIMS),
                                                 dtype=np.float32)),
            "y": jnp.asarray(rng.standard_normal(rows, dtype=np.float32))}


def _sketch_cols(rng, rows):
    return {"item": jnp.asarray(rng.integers(0, 10_000, rows)
                                .astype(np.int32))}


CLASSES = {
    "xtx": (LinregrAggregate, _xtx_cols),
    "sketch": (lambda: CountMinAggregate(4, 128), _sketch_cols),
}


def _time(fn, reps: int) -> float:
    fn()  # compile, untimed
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree.leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return best


def _hlo_context(agg, cols) -> dict:
    """Replayed compiled-HLO statistics for one masked fold over the
    bucket's columns — context metadata only, never consumed by lookup."""
    try:
        mask = jnp.ones(jax.tree.leaves(cols)[0].shape[0], jnp.bool_)

        def fold(c, m):
            return agg.transition(agg.init(c), c, m)

        txt = jax.jit(fold).lower(cols, mask).compile().as_text()
        stats = analyze(txt, {})
        return {"hlo_dot_flops": stats["dot_flops"],
                "hlo_bytes_accessed": stats["bytes_accessed"]}
    except Exception as e:  # HLO text dialects drift across jax releases
        return {"hlo_error": type(e).__name__}


def _skewed_gids(rng, rows: int, groups: int) -> jnp.ndarray:
    w = 1.0 / (np.arange(groups) + 1.0)
    return jnp.asarray(rng.choice(groups, rows, p=w / w.sum())
                       .astype(np.int32))


def _mesh():
    if len(jax.devices()) <= 1:
        return None
    from repro.core.compat import make_mesh
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def measure(rows_list, groups_list, reps: int, block_sizes) -> dict:
    """engines / grouped_block tables (see Calibration's schema)."""
    engines: dict[str, dict[str, list]] = {}
    grouped_block: list = []
    mesh = _mesh()

    def put(engine, cls, entry):
        engines.setdefault(engine, {}).setdefault(cls, []).append(entry)

    for rows in rows_list:
        rng = np.random.default_rng(rows)
        for cls, (make, build) in CLASSES.items():
            cols = build(rng, rows)
            tbl = Table.from_columns(cols)
            base = {"rows": rows, **_hlo_context(make(), cols)}
            s = _time(lambda: run_local(make(), tbl), reps)
            put("local", cls, {**base, "seconds": s})
            print(f"  local/{cls} rows={rows}: {_fmt_s(s)}")
            if mesh is not None:
                dist = tbl.distribute(mesh)
                s = _time(lambda: run_sharded(make(), dist), reps)
                put("sharded", cls, {"rows": rows, "seconds": s})
                print(f"  sharded/{cls} rows={rows}: {_fmt_s(s)}")

            for groups in groups_list:
                gids = _skewed_gids(rng, rows, groups)
                gtbl = Table.from_columns(dict(cols, g=gids))
                view = gtbl.group_by("g", groups)
                gb = {"rows": rows, "groups": groups}
                for method in ("segment", "masked"):
                    s = _time(lambda m=method: run_grouped(
                        make(), view, method=m), reps)
                    put(f"grouped-{method}", cls, {**gb, "seconds": s})
                    print(f"  grouped-{method}/{cls} rows={rows} "
                          f"groups={groups}: {_fmt_s(s)}")
                    if mesh is not None:
                        s = _time(lambda m=method: run_grouped(
                            make(), view, method=m, mesh=mesh), reps)
                        put(f"sharded-grouped-{method}", cls,
                            {**gb, "seconds": s})

        # grouped-block sweep: measured-best segment block size per
        # bucket (class-independent — the xtx workload is the driver)
        make, build = CLASSES["xtx"]
        cols = build(rng, rows)
        for groups in groups_list:
            gtbl = Table.from_columns(
                dict(cols, g=_skewed_gids(rng, rows, groups)))
            view = gtbl.group_by("g", groups)
            timed = {}
            for bs in block_sizes:
                if bs * 2 > max(rows, 1):
                    continue
                timed[bs] = _time(lambda b=bs: run_grouped(
                    make(), view, method="segment", block_size=b), reps)
            if timed:
                best = min(timed, key=timed.get)
                grouped_block.append(
                    {"rows": rows, "groups": groups, "block": best,
                     "heuristic_block": segment_block_size(rows, groups),
                     "sweep": {str(b): s for b, s in timed.items()}})
                print(f"  block sweep rows={rows} groups={groups}: "
                      f"best={best} ({_fmt_s(timed[best])})")

    # generic = mean of the measured classes, cell by cell
    for engine, table in engines.items():
        buckets: dict[tuple, list] = {}
        for entries in table.values():
            for e in entries:
                key = (e["rows"], e.get("groups"))
                buckets.setdefault(key, []).append(e["seconds"])
        table["generic"] = [
            {"rows": r, **({"groups": g} if g is not None else {}),
             "seconds": float(np.mean(ss))}
            for (r, g), ss in sorted(buckets.items())]
    return {"engines": engines, "grouped_block": grouped_block}


def tune_kernels(reps: int) -> dict:
    """TPU-only row-tile sweep for the block kernels the ``supports``
    rankers consult.  Off-TPU the sweep would time interpret mode —
    meaningless for tile choice — so it records nothing."""
    if jax.default_backend() != "tpu":
        return {}
    rng = np.random.default_rng(0)
    n = 1 << 17
    x = jnp.asarray(rng.standard_normal((n, _DIMS), dtype=np.float32))
    y = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
    items = jnp.asarray(rng.integers(0, 10_000, n).astype(np.int32))
    mask = jnp.ones(n, jnp.bool_)
    tuned = {}
    for name, call in (
        ("xtx", lambda t: kernels.dispatch(
            "xtx", x, y, impl="pallas", tile_n=t)),
        ("countmin", lambda t: kernels.dispatch(
            "countmin", items, mask, 4, 128, impl="pallas", tile_n=t)),
    ):
        timed = {t: _time(lambda tt=t: call(tt), reps)
                 for t in (512, 1024, 2048, 4096)}
        best = min(timed, key=timed.get)
        tuned[name] = {"tile_n": best,
                       "sweep": {str(t): s for t, s in timed.items()}}
        print(f"  kernel {name}: tile_n={best} ({_fmt_s(timed[best])})")
    return tuned


def kernel_smoke() -> list:
    """Force every registered Pallas kernel body (interpret mode off-TPU)
    on a tiny layout and record bit-identity against its jnp ref — the
    CI evidence that the compiled path computes the same states."""
    import warnings
    rng = np.random.default_rng(42)
    bs, nb, G = 16, 5, 3
    gids = jnp.asarray(np.append(rng.integers(0, G, nb - 1), G)
                       .astype(np.int32))  # trailing sentinel pad block
    n2 = nb * bs
    valid = jnp.asarray(rng.random(n2) < 0.8)
    x = jnp.asarray((rng.integers(-8, 8, (n2, 3)) / 4).astype(np.float32))
    y = jnp.asarray((rng.integers(-8, 8, n2) / 4).astype(np.float32))
    items = jnp.asarray(rng.integers(0, 500, n2).astype(np.int32))
    mask = jnp.asarray(rng.random(n2) < 0.8)
    cases = {
        "segment_linregr": ((x, y, valid, gids), {"num_groups": G}),
        "segment_countmin": ((items, valid, gids),
                             {"depth": 4, "width": 128, "num_groups": G}),
        "segment_fm": ((items, valid, gids),
                       {"num_hashes": 4, "bits": 32, "num_groups": G}),
        "xtx": ((x, y), {}),
        "countmin": ((items, mask, 4, 128), {}),
    }
    out = []
    for name, (args, kw) in cases.items():
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # forced-pallas interpret note
            got = kernels.dispatch(name, *args, impl="pallas", **kw)
        want = kernels.dispatch(name, *args, impl="ref", **kw)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)))
        out.append({"kernel": name, "impl": "pallas(interpret)"
                    if jax.default_backend() != "tpu" else "pallas",
                    "bit_identical": bool(same)})
        print(f"  kernel smoke {name}: "
              f"{'bit-identical' if same else 'MISMATCH'}")
        if not same:
            raise SystemExit(f"kernel smoke: {name} pallas body diverged "
                             "from its jnp ref")
    return out


def main(argv=None) -> str:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", default="20000,200000",
                    help="comma list of row-bucket sizes")
    ap.add_argument("--groups", default="8,64",
                    help="comma list of group-bucket sizes")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block-sizes", default="256,1024,4096",
                    help="segment block sizes for the grouped sweep")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: one tiny bucket, reps=1")
    ap.add_argument("--interpret", action="store_true",
                    help="also run every Pallas kernel body (interpret "
                         "mode off-TPU) against its ref, bit-exact")
    ap.add_argument("--out", default=None,
                    help="output path (default: "
                         "benchmarks/calibration/<backend>.json)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    backend = jax.default_backend()
    if args.tiny:
        rows_list, groups_list, reps = [4096], [8], 1
        block_sizes = [64, 256]
    else:
        rows_list = [int(r) for r in args.rows.split(",")]
        groups_list = [int(g) for g in args.groups.split(",")]
        reps = args.reps
        block_sizes = [int(b) for b in args.block_sizes.split(",")]

    print(f"calibrating backend={backend} devices={len(jax.devices())} "
          f"rows={rows_list} groups={groups_list} reps={reps}")
    tables = measure(rows_list, groups_list, reps, block_sizes)
    tuned = tune_kernels(reps)
    cal = calibration.Calibration(
        backend=backend,
        timestamp=time.strftime("%Y-%m-%dT%H:%M:%S"),
        engines=tables["engines"],
        kernels=tuned,
        grouped_block=tables["grouped_block"],
    )
    doc = cal.to_dict()
    if args.interpret:
        doc["kernel_smoke"] = kernel_smoke()

    out = args.out or os.path.join(os.path.dirname(__file__),
                                   "calibration", f"{backend}.json")
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out}")
    # round-trip sanity: the file loads and answers a lookup
    cal2 = calibration.load(out)
    probe = cal2.engine_seconds("grouped-segment", "xtx", rows_list[0],
                                groups_list[0])
    print(f"lookup grouped-segment/xtx rows={rows_list[0]} "
          f"groups={groups_list[0]}: "
          f"{'MISSING' if probe is None else _fmt_s(probe)}")
    return out


if __name__ == "__main__":
    main()
