"""Statement kinds are files: ``bench/kinds/<kind>.py``, found by name."""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import data, roofline, spec, statements  # noqa: E402

CFG = {"rows": 6000, "k": 4, "noise": 0.1, "groups": 3,
       "columns": ["x", "y", "g"]}
SEED = 2**32 + 9


def test_grouped_linregr_answers_its_reference_and_least_time():
    """The kind read from its file, at a small size: the reference is the
    float64 least-squares fit of each group, the program's answer lies
    within the benchmark's limits of it, and the least time is linregr's
    work over the v5e's peaks."""
    from repro.core import Session, Table
    st = statements.make({"kind": "grouped_linregr", "group_col": "g"}, CFG)
    assert type(st).__module__ == "bench_kind_grouped_linregr"
    assert (st.kind, st.k, st.groups, st.columns) == (
        "grouped_linregr", 4, 3, ["x", "y", "g"])
    cols = data.make_columns(CFG, SEED)
    ref = st.reference(cols)
    x = np.asarray(cols["x"], np.float64)
    y = np.asarray(cols["y"], np.float64)
    g = np.asarray(cols["g"])
    for gi in range(3):
        coef = np.linalg.lstsq(x[g == gi], y[g == gi], rcond=None)[0]
        np.testing.assert_allclose(ref["coef"][gi], coef, rtol=1e-9)
        assert ref["n"][gi] == np.sum(g == gi)

    sess = Session()
    h = st.issue(sess, Table.from_columns(dict(cols)))
    sess.run()
    gaps = st.gaps(st.answer(h.result()), ref)
    assert gaps["rows"] == 0 and gaps["scale"] < 2e-6, gaps

    assert roofline.least_seconds("grouped_linregr", 6000, 4,
                                  "TPU v5 lite") == max(
        (2 * 6000 * 16 + 2 * 6000 * 4) / 197e12, 6000 * 5 * 4 / 819e9)


def test_a_kind_added_as_a_file_is_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "kinds" / "row_count.py").write_text(
        "class Statement:\n"
        "    def __init__(self, params, cfg):\n"
        "        self.kind = params['kind']\n\n\n"
        "def flops(rows, k):\n    return float(rows)\n\n\n"
        "def bytes(rows, k):\n    return 4.0 * rows\n")
    mod = spec.kind("row_count", bench)
    assert mod.Statement({"kind": "row_count"}, {}).kind == "row_count"
    assert mod.flops(10, 3) == 10.0
    with pytest.raises(FileNotFoundError, match="no_such_kind"):
        spec.kind("no_such_kind", bench)
