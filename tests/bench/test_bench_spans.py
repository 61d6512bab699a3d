"""The per-layer metrics that read the program's own spans
(``planner.plan_ms``, ``layout.index_host_ms``,
``engine.dispatch_host_ms``): host milliseconds inside one ``madjax.*``
span, clipped to the window, per completed statement -- on a hand-made
reduced trace, and on a whole traced run of the cell on the CPU."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import devtrace, harness, spec, spans  # noqa: E402

SPAN_METRICS = {"planner.plan_ms": "madjax.plan",
                "layout.index_host_ms": "madjax.layout.index",
                "engine.dispatch_host_ms": "madjax.fold.dispatch"}


def _ctx(host, done: int = 2, window=(0, 100)) -> harness.Context:
    records = [harness.Record(i, "grouped_linregr", 1000, 4, 0.0, 1.0,
                              raw=object()) for i in range(done)]
    records.append(harness.Record(done, "grouped_linregr", 1000, 4, 0.0))
    trace = {"devices": 1, "modules": [], "ops": [],
             "host": [(devtrace.WINDOW_SPAN, *window)] + host}
    return harness.Context(None, "TPU v5 lite", records, 0.0, trace,
                           window)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_a_span_metric_clips_to_the_window_and_divides_by_statements(
        metric):
    name = SPAN_METRICS[metric]
    # 10 ns before the window and 10 inside; 30 inside; 20 inside and 20
    # after; one outside; another span name that does not count
    host = [(name, -10, 20), (name, 40, 30), (name, 80, 40),
            (name, 150, 5), ("madjax.other", 0, 100)]
    ctx = _ctx(host)
    assert spans.host_ns(ctx.trace, ctx.window_ns, name) == (60, 3)
    read = spec.metric_reader(metric)
    assert read(ctx) == pytest.approx(60 / 1e6 / 2)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_a_span_metric_has_nothing_to_read_without_span_or_statement(
        metric):
    name = SPAN_METRICS[metric]
    read = spec.metric_reader(metric)
    assert read(_ctx([])) is None
    assert read(_ctx([(name, 150, 5)])) is None          # outside the window
    assert read(_ctx([(name, 10, 5)], done=0)) is None


def test_a_traced_run_on_the_cpu_reports_the_span_metrics():
    """The CPU has no device plane, so only the span metrics are read."""
    cell = spec.load_cell("fig4_k80.group_linregr")
    out = harness.run_cell(cell, 2**31 + 5, 0.3, True,
                           t_origin=time.perf_counter(), rows=20_000)
    assert out["correct"], out["checks"]
    for metric in SPAN_METRICS:
        assert out["metrics"][metric]["value"] > 0, metric
        assert out["metrics"][metric]["unit"] == "ms"
