"""The benchmark's operation and byte counts and its table of chip peaks."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from benchlib import roofline  # noqa: E402


def test_linregr_work_by_shape():
    # X^T X is 2 n k^2 operations, X^T y 2 n k; x and y are read once
    assert roofline.linregr_flops(10, 3) == 2 * 10 * 9 + 2 * 10 * 3
    assert roofline.linregr_bytes(10, 3) == 10 * 4 * 4
    assert roofline.linregr_bytes(10, 3, itemsize=8) == 10 * 4 * 8


def test_v5e_peaks_and_the_least_time_of_the_widest_figure4_table():
    p = roofline.peaks("TPU v5 lite")
    assert (p["flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    # 10M x 320 f32: 12.84 GB read at 819 GB/s outlasts 2.05 TFLOP at
    # 197 TFLOP/s, so the statement is bound by bytes
    t = roofline.least_seconds("linregr", 10_000_000, 320, "TPU v5 lite")
    assert t == pytest.approx(10_000_000 * 321 * 4 / 819e9)
    assert t > roofline.linregr_flops(10_000_000, 320) / 197e12
    # grouped and ungrouped statements need the same work
    assert roofline.least_seconds("grouped_linregr", 10_000_000, 80,
                                  "TPU v5e") == pytest.approx(
        10_000_000 * 81 * 4 / 819e9)


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.least_seconds("linregr", 10, 3, "TPU v9 imaginary")


class _Rec:
    raw = "an answer"

    def __init__(self, kind, rows, k):
        self.kind, self.rows, self.k = kind, rows, k


def _recorded_ctx(done):
    """A run's context over the small trace recorded on a v5e."""
    import json
    from benchlib import devtrace, harness
    trace = json.loads((Path(__file__).resolve().parents[2] / "bench"
                        / "testdata" / "trace_small.json").read_text())
    return harness.Context(None, "TPU v5 lite", done, 0.0, trace,
                           devtrace.window_of(trace))


def test_fold_roofline_is_the_least_time_over_the_fold_programs():
    from benchlib import devtrace, spec
    done = [_Rec("grouped_linregr", 20_000, 80)]
    ctx = _recorded_ctx(done)
    fold_ns = devtrace.program_ns(ctx.trace, ctx.window_ns,
                                  ("jit_go", "jit_go_segment"), exact=True)
    want = 100 * roofline.least_seconds("grouped_linregr", 20_000, 80,
                                        "TPU v5 lite") / (fold_ns / 1e9)
    got = spec.metric_reader("fold_roofline")(ctx)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    # nothing completed: nothing to read, never a share of 0
    assert spec.metric_reader("fold_roofline")(_recorded_ctx([])) is None


def test_layout_time_is_per_completed_statement():
    from benchlib import devtrace, spec
    done = [_Rec("grouped_linregr", 20_000, 80)] * 2
    ctx = _recorded_ctx(done)
    ns = devtrace.program_ns(ctx.trace, ctx.window_ns, ("jit_take_rows",))
    got = spec.metric_reader("layout.sort_gather_ms")(ctx)
    assert got == pytest.approx(ns / 1e6 / 2)
    assert spec.metric_reader("layout.sort_gather_ms")(
        _recorded_ctx([])) is None


def test_idle_share_reader_matches_the_reduction():
    from benchlib import devtrace, spec
    ctx = _recorded_ctx([])
    got = spec.metric_reader("device.idle_share.batch")(ctx)
    assert got == pytest.approx(devtrace.idle_share_pct(ctx.trace,
                                                        ctx.window_ns))
    assert 0 < got < 100
