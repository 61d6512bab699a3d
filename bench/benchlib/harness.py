"""One run of one cell: set up, warm up, measure, check, report.

:func:`run_cell` is everything after the look for a chip, so that a test
can drive a whole run on the CPU at a small size.  The order is fixed by
what each step must not see: the measured window meets no program that
set-up did not build (every statement kind was issued once in set-up, and
JAX's persistent cache keeps every program, so what the program traces
again per statement is loaded, and counted in ``host.compile_s.batch``);
``memory_peak_bytes`` is read before the reference runs; the reference
runs after the program's state is freed.

A cell on more than one chip runs as that many segments: its table is
made row-sharded over the first ``chips`` devices, each chip drawing its
own rows, and the reference reads it shard by shard.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np
from jax.sharding import Mesh

from . import data, devtrace, spec, statements, traffic as traffic_mod

CACHE_DIR = spec.ROOT / ".bench_cache" / "jax"
# more statements than any window can finish: the closed loop stops at the
# window's end, and every seed orders the same set
MAX_STATEMENTS = 100_000
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def use_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, with
    every program kept, however fast it compiled."""
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Monitor:
    """Seconds JAX spent making programs (tracing, lowering, compiling or
    loading them from the persistent cache), summed by event."""

    def __init__(self):
        self.total: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        self.total[event] = self.total.get(event, 0.0) + duration

    def compile_seconds(self) -> float:
        return sum(self.total.get(e, 0.0) for e in COMPILE_EVENTS)


@dataclasses.dataclass
class Record:
    index: int                 # catalog entry
    kind: str                  # statement kind (benchlib.statements)
    rows: int                  # input-table rows it reads (0: unknown)
    k: int | None              # independent variables
    t0: float                  # issued
    t1: float | None = None    # result() returned
    raw: object = None         # the answer as the program returned it
    error: str | None = None


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` may look at."""
    cell: spec.Cell
    device_kind: str
    records: list
    compile_s: float                  # making programs, inside the window
    trace: dict | None = None         # devtrace.reduce_xplane(...)
    window_ns: tuple | None = None

    @property
    def done(self) -> list:
        return [r for r in self.records if r.raw is not None]

    def busy_s(self) -> float:
        return devtrace.busy_ns(self.trace, self.window_ns) / 1e9

    def trace_window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": len(jax.devices())}


def memory_peaks() -> list:
    """Each device's peak bytes in use, ``None`` where it reports none."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def cell_mesh(cell: spec.Cell, rows: int | None = None) -> Mesh | None:
    """``None`` for a one-chip cell; else a mesh of the first
    ``cell.chips`` devices on the program's row axis ``"data"``, one
    segment per chip.  Raises where the rows do not divide over them."""
    if cell.chips == 1:
        return None
    n = int(rows or cell.config["rows"])
    if n % cell.chips:
        raise ValueError(f"cell {cell.name}: {n} rows do not divide over "
                         f"its {cell.chips} chips")
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        raise ValueError(f"cell {cell.name} needs {cell.chips} devices; "
                         f"JAX sees {len(devices)}")
    return Mesh(np.asarray(devices), ("data",))


def make_table(cell: spec.Cell, seed: int, mesh: Mesh | None,
               rows: int | None = None):
    """``(columns, table)``: the cell's columns made from ``seed``, and the
    table the traffic reads, over ``mesh``'s chips row by row (Greenplum's
    DISTRIBUTED BY over the cell's segments) where there is one."""
    from repro.core import Table
    cfg = cell.config
    cols = data.make_columns(cfg, seed, rows, mesh)
    jax.block_until_ready(cols)
    named = {k: cols[k] for k in cfg["columns"]}
    if mesh is None:
        return cols, Table.from_columns(named)
    return cols, Table(named, mesh, ("data",))


def _closed_loop(catalog, table, order, seconds: float, session_cls
                 ) -> list:
    """Issue ``catalog[i]`` for each ``i`` of ``order``, each when the
    last one returned, until ``seconds`` have passed; the statement in
    flight then finishes."""
    records = []
    t_start = time.perf_counter()
    for i in order:
        t0 = time.perf_counter()
        if t0 - t_start >= seconds:
            break
        e = catalog[i]
        rec = Record(int(i), e.kind, table.n_rows, e.k, t0)
        try:
            with jax.profiler.TraceAnnotation("bench.statement"):
                sess = session_cls()
                h = e.issue(sess, table)
                sess.run()
                rec.raw = jax.block_until_ready(h.result())
        except Exception as err:                    # counted as failed
            rec.error = f"{type(err).__name__}: {err}"
        rec.t1 = time.perf_counter()
        records.append(rec)
    return records


def _log(msg: str) -> None:
    print(msg, flush=True)


def _quantiles(values) -> str:
    if not values:
        return "none"
    q = np.percentile(values, [50, 95, 100])
    return f"median {q[0]} p95 {q[1]} max {q[2]} (n={len(values)})"


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_origin: float, rows: int | None = None,
             records_out: list | None = None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``rows``
    cuts the table (tests on the CPU); ``t_origin`` is the
    ``time.perf_counter()`` reading of the process's start;
    ``records_out`` receives the window's statement records."""
    from repro.core import Session, trace_execution

    cfg, mix = cell.config, cell.traffic
    mesh = cell_mesh(cell, rows)
    monitor = Monitor()

    # -- set-up: the table, then one statement of each kind in the mix
    cols, table = make_table(cell, seed, mesh, rows)
    catalog = [statements.make(p, cfg) for p in mix["statements"]]
    order = traffic_mod.statement_order(mix, MAX_STATEMENTS, seed)
    warm = _closed_loop(catalog, table, sorted(set(order.tolist())),
                        math.inf, Session)
    for r in warm:
        if r.error:
            raise RuntimeError(f"warm-up statement failed: {r.error}")
    setup_s = time.perf_counter() - t_origin
    _log(f"setup_s={setup_s} (warm-up statements, s: "
         f"{[r.t1 - r.t0 for r in warm]})")

    # -- the measured window
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = monitor.compile_seconds()
    with trace_execution() as ptrace, \
            jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        t_start = time.perf_counter()
        records = _closed_loop(catalog, table, order, seconds, Session)
    compile_s = monitor.compile_seconds() - c0
    if trace:
        jax.profiler.stop_trace()
    if records_out is not None:
        records_out.extend(records)
    done = [r for r in records if r.raw is not None]
    t_end = max((r.t1 for r in records if r.t1 is not None),
                default=t_start)
    window_s = t_end - t_start
    peaks = memory_peaks()
    peak = max((p for p in peaks if p is not None), default=None)
    kernels = sorted({(e.detail.get("name"), e.engine)
                      for e in ptrace.events if e.kind == "kernel"})
    lat_ms = [1e3 * (r.t1 - r.t0) for r in done]
    _log(f"window: {len(records)} statements in {window_s} s; latency ms "
         f"{_quantiles(lat_ms)}; compile_s in window {compile_s}")
    _log(f"peak_bytes_in_use={peak} (per device: {peaks}); "
         f"kernels resolved: {kernels or 'none'}")
    for r in records:
        if r.error:
            _log(f"statement {r.index} ({r.kind}) failed: {r.error}")
            break

    # -- the program's state goes; the reference reads the seed's columns
    del table, ptrace
    gc.collect()
    t_ref = time.perf_counter()
    checks = check_answers(catalog, records, cols, cell.workload["limits"])
    _log(f"reference and comparison took {time.perf_counter() - t_ref} s")
    failed = len(records) - len(done)
    correct = (bool(done) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    dev = dict(device_info(), memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": len(records), "failed": failed}
    if not trace:
        values = {"rows_per_s": (sum(r.rows for r in done) / window_s
                                 if window_s else 0.0),
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        reduced = devtrace.reduce_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = devtrace.window_of(reduced)
        ctx = Context(cell, dev["kind"], records, compile_s, reduced, w)
        out["metrics"] = per_layer_metrics(cell, ctx)
        dev["busy_s"] = ctx.busy_s()
        dev["window_s"] = ctx.trace_window_s()
        out["breakdown"] = {"device_ops": devtrace.top_ops(reduced, w),
                            "idle_gaps": devtrace.idle_gaps(reduced, w)}
        _log("device seconds by program: "
             f"{devtrace.program_seconds(reduced, w)}")
    out["device"] = dev
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return out


def per_layer_metrics(cell: spec.Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check_answers(catalog, records, cols, limits: dict) -> dict:
    """Every answer of the window against the reference, per number
    compared: ``{"<name>_gap": {"value": worst gap over answers,
    "limit": ...}}``.  A number with no answer to read reads infinity.
    The gaps that have no limit are printed, not compared."""
    done = [r for r in records if r.raw is not None]
    used = sorted({r.index for r in done})
    refs = dict(zip(used, statements.fold_reference(
        [catalog[i] for i in used], cols)))
    worst = {k: (0.0 if done else math.inf) for k in limits}
    for r in done:
        e = catalog[r.index]
        for k, v in e.gaps(e.answer(r.raw), refs[r.index]).items():
            worst[k] = max(worst.get(k, 0.0), v)
    _log("gaps not compared: "
         f"{ {k: v for k, v in worst.items() if k not in limits} }")
    return {f"{k}_gap": {"value": worst[k], "limit": limits[k]}
            for k in limits}
