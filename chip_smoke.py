#!/usr/bin/env python3
"""Smoke run of the analytics main path on a TPU.

Drives MADJAX once through the entry points an analyst calls --
``Session``, ``AnalyticsServer``, ``materialize`` and ``Join`` -- on one
seeded table of the size a deployment keeps on a 16 GB v5e chip: the
paper's regression table (Figure 4) at 10M rows of 80 f32 independent
variables, plus ``y``, an int32 ``item`` column and an int32 GROUP BY key
of 64 Zipf-skewed groups.  Every phase is checked against a plain numpy
reference computed on the host from the same seed, in float64 where the
device computes in float32.

    python chip_smoke.py                # one chip: every one-chip phase
    python chip_smoke.py --four-chips   # a 2x2 host: the sharded phases,
                                        # each against the same statements
                                        # on one device

The seconds printed are smoke timings of a single run, not benchmark
numbers.  Without a TPU the script exits non-zero before the first phase.
The last line of a run that passed is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import threading
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.core import (  # noqa: E402
    AnalyticsServer, Join, Session, Table, run_many, trace_execution,
)
from repro.core.compat import make_mesh  # noqa: E402
from repro.core.plan import ScanAgg  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.methods.linregr import LinregrAggregate  # noqa: E402
from repro.methods.logregr import IRLSTask  # noqa: E402
from repro.methods.sketches import CountMinAggregate, FMAggregate  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    rows: int = 10_000_000
    k: int = 80                 # independent variables (x is rows x k f32)
    groups: int = 64            # Zipf-skewed GROUP BY key
    stores: int = 512           # dimension rows of the star join
    items: int = 1_000_000      # item id range
    width: int = 1024           # count-min width
    fm_bits: int = 128          # FM width the compiled segment kernel takes
    irls_rounds: int = 3
    append_rows: int = 100_000  # 1% of rows per living-view append
    sessions: int = 8           # analysts on the server
    chunk: int = 1 << 20        # host reference rows per step


FULL = Size()

DEPTH, HASHES = 4, 8
# the sketches' hash family: murmur3-finalized multiply-shift
PRIMES = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
                   0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09],
                  dtype=np.uint32)

# backend compile seconds, summed over every program JAX compiles (a hit
# in the persistent cache counts its load time instead)
_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


# ---------------------------------------------------------------------------
# Data, made on the host from the seed.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Data:
    x: np.ndarray          # (rows, k) f32
    y: np.ndarray          # (rows,) f32: x @ b + noise
    click: np.ndarray      # (rows,) f32 {0,1}, P(1) = sigmoid(x @ b / sqrt k)
    item: np.ndarray       # (rows,) i32
    g: np.ndarray          # (rows,) i32 in [0, groups), Zipf-skewed
    store_fk: np.ndarray   # (rows,) i32: a dimension key per fact row
    dim_key: np.ndarray    # (stores,) i32, sparse and unique
    dim_region: np.ndarray  # (stores,) i32 in [0, groups)
    appends: list          # living-view deltas: (x, y, item) per append


def make_data(size: Size, seed: int) -> Data:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((size.rows, size.k), dtype=np.float32)
    b = rng.standard_normal(size.k, dtype=np.float32)
    lin = x @ b
    y = lin + np.float32(0.1) * rng.standard_normal(size.rows,
                                                    dtype=np.float32)
    p = 1.0 / (1.0 + np.exp(-lin / np.sqrt(size.k)))
    click = (rng.random(size.rows, dtype=np.float32) < p).astype(np.float32)
    zipf = 1.0 / np.arange(1, size.groups + 1) ** 1.1
    g = rng.choice(size.groups, size.rows, p=zipf / zipf.sum())
    item = rng.integers(0, size.items, size.rows, dtype=np.int32)
    dim_key = rng.choice(1 << 24, size.stores, replace=False)
    dim_region = rng.integers(0, size.groups, size.stores, dtype=np.int32)
    store_fk = dim_key[rng.integers(0, size.stores, size.rows)]
    appends = []
    for _ in range(2):
        xa = rng.standard_normal((size.append_rows, size.k),
                                 dtype=np.float32)
        ya = xa @ b + np.float32(0.1) * rng.standard_normal(
            size.append_rows, dtype=np.float32)
        appends.append((xa, ya, rng.integers(0, size.items, size.append_rows,
                                             dtype=np.int32)))
    return Data(x, y, click, item, g.astype(np.int32),
                store_fk.astype(np.int32), dim_key.astype(np.int32),
                dim_region, appends)


# ---------------------------------------------------------------------------
# The plain numpy reference.
# ---------------------------------------------------------------------------

def _fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _hash(items: np.ndarray, i: int) -> np.ndarray:
    return _fmix32(items.astype(np.uint32) * PRIMES[i] + PRIMES[i])


def ref_countmin(items, gid, groups: int, width: int) -> np.ndarray:
    """(groups, DEPTH, width) exact counters."""
    out = np.zeros((groups, DEPTH, width), np.int64)
    for d in range(DEPTH):
        idx = (_hash(items, d) % np.uint32(width)).astype(np.int64)
        out[:, d] = np.bincount(gid.astype(np.int64) * width + idx,
                                minlength=groups * width).reshape(
                                    groups, width)
    return out


def ref_fm_bitmaps(items, gid, groups: int, bits: int) -> np.ndarray:
    """(groups, HASHES, bits) {0,1}: the lowest set bit of each hash, or
    bit ``bits - 1`` when none of the low ``bits`` is set."""
    out = np.zeros((groups, HASHES, bits), np.int64)
    for i in range(HASHES):
        h = _hash(items, i)
        low = (h & (~h + np.uint32(1))).astype(np.float64)
        r = np.frexp(low)[1].astype(np.int64) - 1     # -1 where h == 0
        r = np.where((r < 0) | (r >= bits), bits - 1, r)
        out[:, i] = np.bincount(gid.astype(np.int64) * bits + r,
                                minlength=groups * bits).reshape(
                                    groups, bits) > 0
    return out


def ref_fm_estimate(bitmap: np.ndarray) -> float:
    bits = bitmap.shape[-1]
    unset = bitmap == 0
    r = np.where(unset.any(axis=-1), unset.argmax(axis=-1), bits)
    return 2.0 ** r.mean() / 0.77351


def _group_slices(gid, groups: int, chunk: int):
    """(group, row indices) in chunks of at most ``chunk`` rows."""
    order = np.argsort(gid, kind="stable")
    bounds = np.searchsorted(gid[order], np.arange(groups + 1))
    for g in range(groups):
        for a in range(bounds[g], bounds[g + 1], chunk):
            yield g, order[a:min(a + chunk, bounds[g + 1])]


def ref_ols_stats(x, y, gid, groups: int, chunk: int) -> dict:
    """Per-group float64 sufficient statistics of OLS."""
    k = x.shape[1]
    s = {"xtx": np.zeros((groups, k, k)), "xty": np.zeros((groups, k)),
         "y_sum": np.zeros(groups), "y_sq": np.zeros(groups),
         "n": np.zeros(groups)}
    for g, idx in _group_slices(gid, groups, chunk):
        xc, yc = x[idx].astype(np.float64), y[idx].astype(np.float64)
        s["xtx"][g] += xc.T @ xc
        s["xty"][g] += xc.T @ yc
        s["y_sum"][g] += yc.sum()
        s["y_sq"][g] += yc @ yc
        s["n"][g] += len(idx)
    return s


def ref_ols(s: dict) -> dict:
    """Coefficients and R^2 from (stacked) OLS statistics."""
    coef = np.linalg.solve(s["xtx"], s["xty"][..., None])[..., 0]
    sse = (s["y_sq"] - 2 * np.einsum("...k,...k", coef, s["xty"])
           + np.einsum("...i,...ij,...j", coef, s["xtx"], coef))
    tss = s["y_sq"] - s["y_sum"] ** 2 / s["n"]
    return {"coef": coef, "r2": 1.0 - sse / tss, "n": s["n"]}


def ref_irls(x, y, gid, groups: int, rounds: int, chunk: int) -> np.ndarray:
    """Per-group IRLS coefficients after ``rounds`` Newton steps from 0."""
    k = x.shape[1]
    beta = np.zeros((groups, k))
    slices = list(_group_slices(gid, groups, chunk))
    for _ in range(rounds):
        xdx, xdz = np.zeros((groups, k, k)), np.zeros((groups, k))
        for g, idx in slices:
            xc, yc = x[idx].astype(np.float64), y[idx].astype(np.float64)
            eta = xc @ beta[g]
            p = 1.0 / (1.0 + np.exp(-eta))
            w = np.maximum(p * (1.0 - p), 1e-10)
            z = eta + (yc - p) / w
            xw = xc * w[:, None]
            xdx[g] += xw.T @ xc
            xdz[g] += xw.T @ z
        beta = np.linalg.solve(xdx + 1e-8 * np.eye(k), xdz[..., None])[..., 0]
    return beta


class Reference:
    """Host results, each computed once, on first use."""

    def __init__(self, data: Data, size: Size):
        self.d, self.size = data, size

    @functools.cached_property
    def by_group(self) -> dict:
        d, s = self.d, self.size
        return ref_ols_stats(d.x, d.y, d.g, s.groups, s.chunk)

    @functools.cached_property
    def ols(self) -> dict:
        return ref_ols({k: v.sum(axis=0) for k, v in self.by_group.items()})

    @functools.cached_property
    def ols_grouped(self) -> dict:
        return ref_ols(self.by_group)

    @functools.cached_property
    def irls(self) -> np.ndarray:
        d, s = self.d, self.size
        return ref_irls(d.x, d.click, d.g, s.groups, s.irls_rounds, s.chunk)

    @functools.cached_property
    def region(self) -> np.ndarray:
        """The join oracle: each fact row's region, by a dictionary
        lookup of its foreign key (materialize, then group)."""
        d = self.d
        lookup = dict(zip(d.dim_key.tolist(), d.dim_region.tolist()))
        return np.vectorize(lookup.__getitem__, otypes=[np.int32])(
            d.store_fk)

    @functools.cached_property
    def ols_joined(self) -> dict:
        d, s = self.d, self.size
        return ref_ols(ref_ols_stats(d.x, d.y, self.region, s.groups,
                                     s.chunk))

    def countmin(self, gid=None, groups: int = 1, items=None) -> np.ndarray:
        items = self.d.item if items is None else items
        gid = np.zeros(len(items), np.int32) if gid is None else gid
        return ref_countmin(items, gid, groups, self.size.width)

    def fm(self, bits: int, gid=None, groups: int = 1) -> np.ndarray:
        gid = np.zeros(len(self.d.item), np.int32) if gid is None else gid
        return ref_fm_bitmaps(self.d.item, gid, groups, bits)


# ---------------------------------------------------------------------------
# Checks and phase bookkeeping.
# ---------------------------------------------------------------------------

class Checks:
    def __init__(self):
        self.failed: list[str] = []
        self.phase = ""

    def __call__(self, what: str, ok: bool, detail: str = "") -> bool:
        ok = bool(ok)
        print(f"  [{'ok' if ok else 'FAIL'}] {self.phase}: {what}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(f"{self.phase}: {what}")
        return ok

    def close(self, what: str, got, want, *, rtol: float, atol: float):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        err = float(np.max(np.abs(got - want))) if got.size else 0.0
        ok = got.shape == want.shape and np.allclose(got, want, rtol=rtol,
                                                     atol=atol)
        return self(what, ok, f"max abs err {err:.3g}, rtol {rtol}, "
                              f"atol {atol}")

    def equal(self, what: str, got, want):
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape:
            return self(what, False, f"shape {got.shape} != {want.shape}")
        diff = int(np.sum(got != want))
        return self(what, diff == 0,
                    f"{diff} values differ" if diff else "bit-identical")


def _sync(out):
    jax.block_until_ready(jax.tree.leaves(out))
    return out


def run_phase(checks: Checks, name: str, first, steady=None, *,
              repeat: bool = True):
    """Run ``first`` (compiling what it needs), then -- with ``repeat`` --
    ``steady`` (defaults to ``first`` again), and print both times.
    Returns the last output, or None when the phase raised."""
    checks.phase = name
    steady = steady or first
    try:
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        out = _sync(first())
        c1, t1 = _COMPILE_S[0], time.perf_counter()
        if repeat:
            out = _sync(steady())
        c2, t2 = _COMPILE_S[0], time.perf_counter()
    except Exception:
        traceback.print_exc()
        checks("phase ran to its end", False)
        return None
    second = (f"steady_s={t2 - t1:.3f} (compile_s={c2 - c1:.3f})" if repeat
              else "steady_s=not measured")
    print(f"phase {name}: first_s={t1 - t0:.3f} (compile_s={c1 - c0:.3f}) "
          f"{second} peak_bytes_in_use={_peak_bytes()} "
          "[smoke timings of one run, not benchmark numbers]", flush=True)
    return out


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _engines(trace, kind: str) -> list[str]:
    return [e.engine for e in trace.events if e.kind == kind]


def _kernels(trace) -> dict:
    return {e.detail["name"]: e.engine for e in trace.kernels}


# ---------------------------------------------------------------------------
# One-chip phases.
# ---------------------------------------------------------------------------

def phase_session(checks, ref, cols):
    """One Session batch -- profile + linregr + countmin + FM -- as ONE
    scan of the table."""
    tbl = Table.from_columns(cols)
    size = ref.size

    def run():
        sess = Session()
        hs = [sess.profile(tbl), sess.linregr(tbl),
              sess.countmin_sketch(tbl, width=size.width),
              sess.fm_distinct_count(tbl)]
        with trace_execution() as t:
            sess.run()
        return [h.result() for h in hs], t

    out = run_phase(checks, "session", run)
    if out is None:
        return None
    (prof, lr, cm, fm), t = out
    checks("one data pass for the batch", len(t.scans) == 1,
           f"{len(t.scans)} scans")
    checks.close("linregr coef vs f64 normal equations", lr.coef,
                 ref.ols["coef"], rtol=1e-3, atol=1e-3)
    # R^2 = 1 - sse/tss with sse = y'y - coef.X'y: two f32 sums of 10M
    # terms, about 8e8 each, that cancel to about 1e5 -- their ~1e-4
    # relative rounding leaves R^2 good to a few 1e-4
    checks.close("linregr r2", lr.r2, ref.ols["r2"], rtol=0, atol=5e-4)
    checks.equal("linregr rows", lr.num_rows, float(size.rows))
    checks.equal("countmin counters", cm, ref.countmin()[0])
    # the estimate 2**mean(r) / 0.77351 is taken in f32 on the device
    checks.close("FM estimate", fm, ref_fm_estimate(ref.fm(32)[0]),
                 rtol=1e-5, atol=0)
    y = ref.d.y
    checks.equal("profile y count", prof["y"]["count"], float(size.rows))
    checks.equal("profile y min/max",
                 [prof["y"]["min"], prof["y"]["max"]], [y.min(), y.max()])
    checks.close("profile y mean", prof["y"]["mean"],
                 y.astype(np.float64).mean(), rtol=0, atol=1e-4)
    return prof, lr, cm, fm


def phase_grouped(checks, ref, cols):
    """GROUP BY over 64 Zipf-skewed groups: a grouped linregr scan and a
    few IRLS rounds of fit_grouped, both on the partitioned segment
    engine."""
    size = ref.size

    def run():
        # tables are built per run, so each run's partitioning sort and
        # group-aligned layout are its own and free when it returns
        tbl = Table.from_columns({"x": cols["x"], "y": cols["y"],
                                  "g": cols["g"]})
        sess = Session()
        h_lr = sess.grouped_scan(LinregrAggregate(), tbl, "g", size.groups,
                                 columns={"x": "x", "y": "y"})
        with trace_execution() as t1:
            sess.run()
        lr = h_lr.result()
        del tbl, sess
        clicks = Table.from_columns({"x": cols["x"], "y": cols["click"],
                                     "g": cols["g"]})
        sess = Session()
        h_fit = sess.fit(IRLSTask(), clicks, group_col="g",
                         num_groups=size.groups,
                         max_iters=size.irls_rounds, tol=None)
        with trace_execution() as t2:
            sess.run()
        return lr, h_fit.result(), t1, t2

    out = run_phase(checks, "grouped", run)
    if out is None:
        return
    lr, fit, t1, t2 = out
    checks("grouped scan engine", _engines(t1, "scan") == ["grouped-segment"],
           str(_engines(t1, "scan")))
    checks("fit_grouped engine", _engines(t2, "fit") == ["grouped-segment"],
           str(_engines(t2, "fit")))
    checks.equal("rows per group", lr.num_rows, ref.ols_grouped["n"])
    checks.close("grouped linregr coef", lr.coef, ref.ols_grouped["coef"],
                 rtol=1e-3, atol=1e-3)
    checks.close("grouped linregr r2", lr.r2, ref.ols_grouped["r2"],
                 rtol=0, atol=5e-4)
    checks.equal("IRLS rounds per group", fit.n_iters,
                 np.full(size.groups, size.irls_rounds))
    checks.close(f"IRLS coef after {size.irls_rounds} rounds",
                 fit.result["coef"], ref.irls, rtol=1e-3, atol=1e-3)


def phase_join(checks, ref, cols):
    """Star join: the fact table JOIN a 512-row store dimension GROUP BY
    the store's region, through Join + joined_grouped_scan."""
    size, d = ref.size, ref.d
    fact = Table.from_columns({"x": cols["x"], "y": cols["y"],
                               "item": cols["item"],
                               "store_fk": cols["store_fk"]})
    dim = Table.from_columns({"store_id": d.dim_key,
                              "region": d.dim_region})

    def run():
        sess = Session()
        j = Join(fact, dim, "store_fk", "store_id", "region")
        h_lr = sess.joined_grouped_scan(LinregrAggregate(), j, size.groups,
                                        columns={"x": "x", "y": "y"})
        h_cm = sess.joined_grouped_scan(
            CountMinAggregate(DEPTH, size.width), j, size.groups,
            columns=("item",))
        with trace_execution() as t:
            sess.run()
        return h_lr.result(), h_cm.result(), t

    out = run_phase(checks, "join", run)
    if out is None:
        return
    lr, cm, t = out
    # the first run resolved the join; this (steady) run found it memoized
    checks("one grouped pass for both statements",
           _engines(t, "scan") == ["grouped-segment"],
           str(_engines(t, "scan")))
    checks.equal("rows per region", lr.num_rows, ref.ols_joined["n"])
    checks.close("joined linregr coef vs materialize-then-group oracle",
                 lr.coef, ref.ols_joined["coef"], rtol=1e-3, atol=1e-3)
    checks.equal("joined countmin counters", cm,
                 ref.countmin(ref.region, size.groups))


def phase_living_view(checks, ref, cols):
    """materialize, then append 1% more rows (twice), then result(): a
    delta refresh whose retained state equals a rescan's."""
    size, d = ref.size, ref.d
    tbl = Table.from_columns({"x": cols["x"], "y": cols["y"],
                              "item": cols["item"]})
    nodes = [ScanAgg(LinregrAggregate(), tbl, columns={"x": "x", "y": "y"}),
             ScanAgg(CountMinAggregate(DEPTH, size.width), tbl,
                     columns=("item",)),
             ScanAgg(FMAggregate(HASHES, 32), tbl, columns=("item",))]
    view = []
    traces = []

    def append_and_read(i):
        xa, ya, ia = d.appends[i]
        tbl.append({"x": xa, "y": ya, "item": ia})
        with trace_execution() as t:
            res = view[0].result()
        traces.append(t)
        return res

    def first():
        view.append(Session().materialize(*nodes))
        return append_and_read(0)

    out = run_phase(checks, "living_view", first,
                    lambda: append_and_read(1))
    if out is None:
        return
    lr, cm, fm = out
    for i, t in enumerate(traces):
        checks(f"append {i + 1} refreshed by a delta fold, no rescan",
               len(t.deltas) == 1 and not t.scans,
               f"{len(t.deltas)} deltas, {len(t.scans)} scans")
    rescan = run_many(view[0].members, tbl, finalize=False)
    retained = view[0]._state
    checks.equal("retained countmin state == rescan", retained[1], rescan[1])
    checks.equal("retained FM state == rescan", retained[2], rescan[2])
    for leaf in ("xtx", "xty", "y_sum", "y_sq", "n"):
        # f32 sums of 10M terms, in another order: a running sum near
        # 1e7 rounds by ~1 per term, ~1e-4 of the leaf's scale in all
        scale = float(np.max(np.abs(rescan[0][leaf])))
        checks.close(f"retained linregr {leaf} vs rescan",
                     retained[0][leaf], rescan[0][leaf], rtol=0,
                     atol=5e-4 * scale)
    items = np.concatenate([d.item] + [a[2] for a in d.appends])
    checks.equal("countmin counters after appends", cm,
                 ref.countmin(items=items)[0])
    xs = [a[0] for a in d.appends]
    ys = [a[1] for a in d.appends]
    delta = ref_ols_stats(np.concatenate(xs), np.concatenate(ys),
                          np.zeros(sum(map(len, ys)), np.int32), 1,
                          size.chunk)
    both = {k: v.sum(axis=0) + delta[k][0]
            for k, v in ref.by_group.items()}
    checks.close("linregr coef after appends", lr.coef,
                 ref_ols(both)["coef"], rtol=1e-3, atol=1e-3)


def phase_server(checks, ref, cols, session_results):
    """AnalyticsServer(drain="thread"): 8 analyst sessions x 4 statements,
    every handle resolved with result()."""
    size = ref.size
    tbl = Table.from_columns(cols)
    server = AnalyticsServer(window_size=64, drain="thread",
                             window_timeout=0.01)

    def analyst(i, results, errors):
        try:
            sess = Session(server=server)
            hs = [sess.profile(tbl), sess.linregr(tbl),
                  sess.countmin_sketch(tbl, width=size.width),
                  sess.fm_distinct_count(tbl)]
            results[i] = [h.result(timeout=600) for h in hs]
        except BaseException as e:  # re-raised on the main thread
            errors[i] = e

    def run():
        server.clear_cache()
        results = [None] * size.sessions
        errors = [None] * size.sessions
        with trace_execution() as t:
            threads = [threading.Thread(target=analyst,
                                        args=(i, results, errors))
                       for i in range(size.sessions)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
        for e in errors:
            if e is not None:
                raise e
        return results, t

    try:
        out = run_phase(checks, "server", run)
    finally:
        server.close()
    if out is None:
        return
    results, t = out
    summ = t.summary()
    n = 4 * size.sessions
    checks(f"{n} statements shared scans", 0 < summ.get("scan", 0) < n,
           f"scans={summ.get('scan', 0)} windows={summ.get('admission', 0)}")
    if session_results is None:
        checks("session phase results to compare with", False)
        return
    prof, lr, cm, fm = session_results
    for i, (p, l, c, f) in enumerate(results):
        checks.equal(f"analyst {i} countmin == Session batch", c, cm)
        checks.equal(f"analyst {i} FM == Session batch", f, fm)
        checks.close(f"analyst {i} linregr coef", l.coef, lr.coef,
                     rtol=1e-5, atol=1e-6)
        checks.close(f"analyst {i} profile y mean", p["y"]["mean"],
                     prof["y"]["mean"], rtol=1e-5, atol=1e-6)


def phase_kernels(checks, ref, cols):
    """The Pallas kernels through the main path, forced, against the jnp
    references: segment kernels under GROUP BY, xtx and countmin in an
    ungrouped scan."""
    size = ref.size

    def ungrouped(impl):
        tbl = Table.from_columns({"x": cols["x"], "y": cols["y"],
                                  "item": cols["item"]})
        sess = Session()
        h_lr = sess.linregr(tbl, use_kernel=impl)
        h_cm = sess.scan(CountMinAggregate(DEPTH, size.width,
                                           use_kernel=impl),
                         tbl, columns=("item",))
        with trace_execution() as t:
            sess.run()
        return h_lr.result(), h_cm.result(), _kernels(t)

    def grouped(impl):
        tbl = Table.from_columns(cols)
        out = []
        kernels = {}
        # one statement per pass: a fused multi-statement pass runs the
        # jnp segment fold, a single one the aggregate's own kernel
        for agg, proj in [
                (LinregrAggregate(impl), {"x": "x", "y": "y"}),
                (CountMinAggregate(DEPTH, size.width, use_kernel=impl),
                 ("item",)),
                (FMAggregate(HASHES, size.fm_bits, use_kernel=impl),
                 ("item",))]:
            sess = Session()
            h = sess.grouped_scan(agg, tbl, "g", size.groups, columns=proj)
            with trace_execution() as t:
                sess.run()
            out.append(h.result())
            kernels.update(_kernels(t))
        return out, kernels

    res = {}
    for impl in ("pallas", "ref"):
        out = run_phase(checks, f"kernels_{impl}",
                        lambda: (ungrouped(impl), grouped(impl)))
        if out is None:
            return
        res[impl] = out
        (_, _, k_un), (_, k_gr) = out
        resolved = dict(k_un, **k_gr)
        print(f"  kernels resolved for use_kernel={impl!r}: {resolved}",
              flush=True)
        checks("every kernel resolved to the forced implementation",
               len(resolved) == 5 and set(resolved.values()) == {impl},
               str(resolved))
    checks.phase = "kernels"
    (lr_p, cm_p, _), (g_p, _) = res["pallas"]
    (lr_r, cm_r, _), (g_r, _) = res["ref"]
    checks.close("xtx linregr coef: pallas vs ref", lr_p.coef, lr_r.coef,
                 rtol=1e-4, atol=1e-5)
    checks.equal("countmin: pallas vs ref", cm_p, cm_r)
    checks.equal("countmin vs host", cm_p, ref.countmin()[0])
    checks.close("segment_linregr coef: pallas vs ref", g_p[0].coef,
                 g_r[0].coef, rtol=1e-3, atol=1e-4)
    checks.close("segment_linregr coef vs host", g_p[0].coef,
                 ref.ols_grouped["coef"], rtol=1e-3, atol=1e-3)
    checks.equal("segment_countmin: pallas vs ref", g_p[1], g_r[1])
    checks.equal("segment_countmin vs host", g_p[1],
                 ref.countmin(ref.d.g, size.groups))
    checks.equal("segment_fm: pallas vs ref", g_p[2], g_r[2])
    bitmaps = ref.fm(size.fm_bits, ref.d.g, size.groups)
    checks.close("segment_fm estimates vs host", g_p[2],
                 [ref_fm_estimate(b) for b in bitmaps], rtol=1e-5, atol=0)


def run_one_chip(size: Size, seed: int, checks: Checks) -> None:
    t0 = time.perf_counter()
    data = make_data(size, seed)
    cols = {"x": data.x, "y": data.y, "item": data.item, "g": data.g}
    dev = {k: jax.device_put(v) for k, v in
           dict(cols, click=data.click, store_fk=data.store_fk).items()}
    _sync(dev)
    print(f"data: {size.rows} rows, k={size.k}, {size.groups} groups, "
          f"{size.stores}-row dimension, made and placed in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    ref = Reference(data, size)
    base = {k: dev[k] for k in cols}
    session_results = phase_session(checks, ref, base)
    phase_grouped(checks, ref, dev)
    phase_living_view(checks, ref, dev)
    phase_server(checks, ref, base, session_results)
    phase_kernels(checks, ref, base)
    # last: the join memo keeps its joined table's layout alive
    phase_join(checks, ref, dev)


# ---------------------------------------------------------------------------
# Four chips: the two-phase parallel GROUP BY across a 4-device mesh.
# ---------------------------------------------------------------------------

def _bytes_in_use(devices) -> list:
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def run_four_chips(size: Size, seed: int, checks: Checks,
                   devices) -> None:
    t0 = time.perf_counter()
    data = make_data(size, seed)
    mesh = make_mesh((4,), ("data",), devices=devices)
    cols = {"x": data.x, "y": data.y, "item": data.item, "g": data.g,
            "click": data.click, "store_fk": data.store_fk}
    one = {k: jax.device_put(v, devices[0]) for k, v in cols.items()}
    dist = Table.from_columns(one).distribute(mesh).columns
    _sync((one, dist))
    print(f"data: {size.rows} rows, k={size.k}, made and placed in "
          f"{time.perf_counter() - t0:.3f} s; bytes_in_use per device "
          f"{_bytes_in_use(devices)}", flush=True)
    dim = Table.from_columns({"store_id": data.dim_key,
                              "region": data.dim_region})

    def table(c, names, ys="y", distribute=False):
        t = Table({n: c[ys if n == "y" else n] for n in names})
        return Table(t.columns, mesh, ("data",)) if distribute else t

    def grouped(distribute):
        tbl = table(dist if distribute else one, ("x", "y", "item", "g"),
                    distribute=distribute)
        sess = Session()
        hs = [sess.grouped_scan(LinregrAggregate(), tbl, "g", size.groups,
                                columns={"x": "x", "y": "y"}),
              sess.grouped_scan(CountMinAggregate(DEPTH, size.width), tbl,
                                "g", size.groups, columns=("item",)),
              sess.grouped_scan(FMAggregate(HASHES, 32), tbl, "g",
                                size.groups, columns=("item",))]
        with trace_execution() as t:
            sess.run()
        return [h.result() for h in hs], t

    def fit(distribute):
        tbl = table(dist if distribute else one, ("x", "y", "g"), "click",
                    distribute)
        sess = Session()
        h = sess.fit(IRLSTask(), tbl, group_col="g", num_groups=size.groups,
                     max_iters=size.irls_rounds, tol=None)
        with trace_execution() as t:
            sess.run()
        return h.result(), t

    def joined(distribute):
        fact = table(dist if distribute else one,
                     ("x", "y", "item", "store_fk"), distribute=distribute)
        j = Join(fact, dim, "store_fk", "store_id", "region")
        sess = Session()
        hs = [sess.joined_grouped_scan(LinregrAggregate(), j, size.groups,
                                       columns={"x": "x", "y": "y"}),
              sess.joined_grouped_scan(CountMinAggregate(DEPTH, size.width),
                                       j, size.groups, columns=("item",))]
        with trace_execution() as t:
            sess.run()
        return [h.result() for h in hs], t

    for name, fn in (("grouped", grouped), ("fit_grouped", fit),
                     ("joined", joined)):
        res = {}
        for distribute in (True, False):
            tag = f"{name}_{'sharded' if distribute else 'one_device'}"
            # one run each: four chips cost four times one
            out = run_phase(checks, tag, lambda: fn(distribute),
                            repeat=False)
            if out is None:
                break
            res[distribute] = out
            print(f"  bytes_in_use per device after {tag}: "
                  f"{_bytes_in_use(devices)}", flush=True)
        if len(res) < 2:
            continue
        checks.phase = name
        (sh, t_sh), (lo, _) = res[True], res[False]
        sharded = [e.detail.get("sharded") for e in t_sh.events
                   if e.kind in ("scan", "fit")]
        checks("ran on the sharded engine", sharded == [True], str(sharded))
        # f32 statistics summed per chip, then across chips: another
        # order than one device's, ~1e-4 relative at 10M rows
        if name == "fit_grouped":
            checks.close("IRLS coef: 4 chips vs 1", sh.result["coef"],
                         lo.result["coef"], rtol=1e-3, atol=1e-4)
            continue
        checks.close("linregr coef: 4 chips vs 1", sh[0].coef, lo[0].coef,
                     rtol=1e-3, atol=1e-4)
        checks.equal("countmin: 4 chips vs 1", sh[1], lo[1])
        if name == "grouped":
            checks.equal("FM: 4 chips vs 1", sh[2], lo[2])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phases on a 4-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
              "devices only); nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: {devices[0].device_kind} x {len(devices)}; "
          f"table k={FULL.k} (no cut)", flush=True)

    checks = Checks()
    if args.four_chips:
        run_four_chips(FULL, args.seed, checks, devices[:4])
    else:
        run_one_chip(FULL, args.seed, checks)
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
