"""The benchmark's reduction from a profiler trace to busy time, idle share,
program time and idle gaps, on hand-made traces of one chip and of two,
and on a small trace recorded on a TPU v5e
(``bench/testdata/trace_small.json``)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import devtrace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "trace_small.json"


def _hand_made() -> dict:
    # window 0..100 ns; programs at 10-30, 20-40 (overlapping), 60-70 and
    # 95-120 (cut by the window's end)
    return {
        "devices": 1,
        "modules": [("jit_go", 10, 20), ("jit_take_rows", 20, 20),
                    ("jit_go_segment", 60, 10), ("jit_take_rows", 95, 25)],
        "ops": [("fusion.1", 10, 15), ("fusion.2", 25, 5),
                ("gather.3", 20, 20), ("dot.4", 60, 10)],
        "host": [(devtrace.WINDOW_SPAN, 0, 100), ("bench.statement", 0, 50),
                 ("PjitFunction(go)", 42, 15), ("bench.statement", 50, 50)],
    }


def test_union_merges_overlaps_and_sorts():
    assert devtrace.union([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [
        (1, 4.5), (5, 6)]


def test_busy_and_idle_share_of_the_hand_made_trace():
    t = _hand_made()
    w = devtrace.window_of(t)
    assert w == (0, 100)
    # 10-40, 60-70 and 95-100 busy
    assert devtrace.busy_ns(t, w) == 45
    assert devtrace.idle_share_pct(t, w) == pytest.approx(55.0)


def test_busy_is_averaged_over_devices():
    t = dict(_hand_made(), devices=2)
    assert devtrace.busy_ns(t, (0, 100)) == 22.5


def test_program_time_by_name_and_by_prefix():
    t = _hand_made()
    w = (0, 100)
    assert devtrace.program_ns(t, w, ("jit_go",), exact=True) == 20
    assert devtrace.program_ns(t, w, ("jit_go",)) == 30    # + go_segment
    assert devtrace.program_ns(t, w, ("jit_take_rows",)) == 25
    secs = devtrace.program_seconds(t, w)
    assert secs["jit_take_rows"] == pytest.approx(25e-9)


def test_top_ops_are_named_with_their_program():
    ops = dict(devtrace.top_ops(_hand_made(), (0, 100)))
    assert ops == pytest.approx({"jit_go/fusion.1": 15e-9,
                                 "jit_take_rows/fusion.2": 5e-9,
                                 "jit_take_rows/gather.3": 20e-9,
                                 "jit_go_segment/dot.4": 10e-9})


def test_idle_gaps_name_the_host_activity_around_them():
    gaps = devtrace.idle_gaps(_hand_made(), (0, 100))
    # 70-95 (25 ns), 40-60 (20), 0-10 (10)
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 20e-9, 10e-9])
    assert gaps[0][0] == "bench.statement"
    assert gaps[1][0] == "PjitFunction(go)"


def test_op_and_program_names_are_shortened():
    assert devtrace.op_name(
        "%fusion.627 = f32[320,320]{1,0} fusion(f32[10,320] %x), "
        "kind=kOutput") == "fusion.627"
    assert devtrace.program_name("jit_go(15000830171644398841)") == "jit_go"


def test_recorded_chip_trace_reduces_consistently():
    t = json.loads(RECORDED.read_text())
    w = devtrace.window_of(t)
    busy = devtrace.busy_ns(t, w)
    assert 0 < busy <= w[1] - w[0]
    idle = devtrace.idle_share_pct(t, w)
    assert idle == pytest.approx(100 * (1 - busy / (w[1] - w[0])))
    # every program's time lies inside the busy time, and together they
    # cover it (programs on one TPU core do not overlap)
    secs = devtrace.program_seconds(t, w)
    assert sum(secs.values()) * 1e9 == pytest.approx(busy, rel=1e-6)
    assert devtrace.program_ns(t, w, ("jit_go",), exact=True) > 0
    ops = devtrace.top_ops(t, w)
    assert ops and all(name.count("/") >= 1 for name, _ in ops)
    gaps = devtrace.idle_gaps(t, w)
    assert sum(g for _, g in gaps) * 1e9 <= (w[1] - w[0]) - busy + 1


def _two_chips() -> dict:
    # window 0..100 ns; both chips run a program through 0-50, chip 1's
    # starting later; an op on chip 0 after chip 1's program began
    return {
        "devices": 2,
        "modules": [("jit_a", 0, 50, 0), ("jit_b", 5, 45, 1)],
        "ops": [("fusion.1", 10, 10, 0), ("dot.2", 30, 20, 1)],
        "host": [(devtrace.WINDOW_SPAN, 0, 100), ("bench.statement", 0, 100)],
    }


def test_device_time_of_two_chips_is_read_per_chip():
    t = _two_chips()
    w = devtrace.window_of(t)
    # each chip is busy through half of the window: 50% idle, not the 75%
    # that one union over both chips, averaged over two, would read
    assert devtrace.busy_ns(t, w) == 47.5
    t["modules"][1] = ("jit_b", 0, 50, 1)
    assert devtrace.busy_ns(t, w) == 50
    assert devtrace.idle_share_pct(t, w) == pytest.approx(50.0)
    # program time is the mean per chip
    assert devtrace.program_ns(t, w, ("jit_",)) == 50
    assert devtrace.program_seconds(t, w) == pytest.approx(
        {"jit_a": 25e-9, "jit_b": 25e-9})
    # an op belongs to the program on its own chip, in seconds per chip
    assert dict(devtrace.top_ops(t, w)) == pytest.approx(
        {"jit_a/fusion.1": 5e-9, "jit_b/dot.2": 10e-9})
    # an idle gap is a stretch in which no chip runs a program
    assert devtrace.idle_gaps(t, w) == [["bench.statement", 50e-9]]


def test_the_recorded_one_chip_trace_reads_as_it_did():
    """Readings of the recorded trace by the reduction as it was before
    device time was read per chip, kept exactly."""
    t = json.loads(RECORDED.read_text())
    w = devtrace.window_of(t)
    assert devtrace.busy_ns(t, w) == 242887909.0
    assert devtrace.idle_share_pct(t, w) == 44.89269061985816
    assert devtrace.program_ns(t, w, ("jit_go", "jit_go_segment"),
                               exact=True) == 17836818.0
    assert devtrace.program_ns(
        t, w, ("jit_take_rows", "jit_argsort", "jit_sort")) == 225048739.0
    assert devtrace.program_seconds(t, w) == {
        "jit_go": 0.000815932,
        "jit_convert_element_type": 1.1879999999999999e-06,
        "jit_broadcast_in_dim": 1.164e-06,
        "jit_take_rows": 0.225048739,
        "jit_go_segment": 0.017020886}
    assert devtrace.top_ops(t, w) == [
        ["jit_take_rows/while", 0.221774157],
        ["jit_take_rows/fusion.2", 0.21730480599999996],
        ["jit_go_segment/custom-call.2", 0.014915717],
        ["jit_take_rows/fusion", 0.003270931],
        ["jit_take_rows/dynamic_update_slice.2", 0.003238722],
        ["jit_go_segment/while.5", 0.002006857],
        ["jit_take_rows/dynamic-slice_reduce_fusion.2",
         0.0010447429999999999],
        ["jit_go_segment/fusion.33", 0.0007822770000000001],
        ["jit_go/custom-call.2", 0.000433296],
        ["jit_go/fusion.15", 0.000351219]]
    assert devtrace.idle_gaps(t, w) == [
        ["lower_sharding_computation", 0.094113174],
        ["lower_sharding_computation", 0.079263932],
        ["bench.statement", 0.016751879], ["bench.statement", 0.005276971],
        ["bench.statement", 0.002309553], ["bench.statement", 0.000136842],
        ["bench.statement", 8.935e-06], ["bench.statement", 2.017e-06],
        ["bench.statement", 1.821e-06], ["bench.statement", 1.4e-06]]
