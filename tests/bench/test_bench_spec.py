"""The harness finds a cell by its name alone, refuses to measure without a
TPU, and takes a new configuration, traffic mix, cell and per-layer metric
as new files, with no file that is already there edited."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import spec  # noqa: E402


def test_every_cell_of_the_benchmark_loads_by_name():
    bench = spec.load_benchmark()
    names = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"] in names
        assert cell.traffic["statements"]
        assert set(cell.workload["limits"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
            assert m["moves"] in e2e


def test_config_files_match_benchmark_json():
    for c in spec.load_benchmark()["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("no_such.cell")


def _no_result_line(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_without_a_tpu_the_harness_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "fig4_k80.group_linregr", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)
    assert "Nothing was measured" in p.stderr


def test_a_checkout_of_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in spec.load_benchmark()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "fig4_k80.group_linregr", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    """Copy the benchmark, then add a configuration, a traffic mix, a cell
    and a per-layer metric as new files, and name them in the copy's
    BENCHMARK.json: the harness finds each by its name."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "rows": 1000, "k": 4, "noise": 0.1, "groups": 2,
         "columns": ["x", "y", "g"]}))
    (bench / "traffic" / "twice.json").write_text(json.dumps(
        {"statements": [{"kind": "grouped_linregr"}], "weights": [1]}))
    (bench / "workloads" / "tiny.twice.json").write_text(json.dumps(
        {"limits": {"rows": 0}}))
    (bench / "metrics" / "rows_seen.py").write_text(
        "def read(ctx):\n    return float(sum(r.rows for r in ctx.done))\n")
    doc = spec.load_benchmark()
    doc["configs"].append({"name": "tiny", "file": "bench/configs/tiny.json"})
    doc["workloads"].append({"name": "tiny.twice", "config": "tiny",
                             "traffic": "twice", "chips": 1})
    doc["per_layer"].append({"name": "rows_seen", "unit": "rows",
                             "moves": "rows_per_s",
                             "workloads": ["tiny.twice"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())
    cell = spec.load_cell("tiny.twice", root=tmp_path)
    assert cell.config["rows"] == 1000
    assert cell.traffic["statements"] == [{"kind": "grouped_linregr"}]
    assert [m["name"] for m in cell.per_layer] == ["rows_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"rows_per_s",
                                                    "setup_s"}

    class _Rec:
        rows = 1000

    class _Ctx:
        done = [_Rec(), _Rec()]

    read = spec.metric_reader("rows_seen", bench=bench)
    assert read(_Ctx()) == 2000.0
