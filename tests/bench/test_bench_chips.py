"""A cell on more than one chip runs as that many segments.

Each run of a cell on four chips is driven in a subprocess on four CPU
devices (``--xla_force_host_platform_device_count=4``), so that this
process keeps its one device.  The cell is made of files alone, in a
temporary copy of the benchmark's directories: k=8, 25 groups, 20,000
rows, four segments.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import data, harness, spec  # noqa: E402

SEED = 2**31 + 11
CONFIG = {"name": "tiny4", "rows": 20_000, "k": 8, "noise": 0.1,
          "groups": 25, "columns": ["x", "y", "g"]}


def _cell_files(tmp: Path, rows: int) -> dict:
    """A four-chip cell's files under ``tmp`` and its ``workloads``
    entry; the traffic mix is the benchmark's own."""
    for sub in ("configs", "traffic", "workloads"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "configs" / "tiny4.json").write_text(
        json.dumps(dict(CONFIG, rows=rows)))
    (tmp / "traffic" / "group_linregr.json").write_text(
        (ROOT / "bench" / "traffic" / "group_linregr.json").read_text())
    (tmp / "workloads" / "tiny4.group_linregr.json").write_text(
        json.dumps({"limits": {"rows": 0, "scale": 2e-6}}))
    return {"name": "tiny4.group_linregr", "config": "tiny4",
            "traffic": "group_linregr", "chips": 4}


def _run_4dev(code: str, tmp: Path, timeout: int = 600) -> dict:
    """Run ``code`` on four CPU devices; it prints one JSON line last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    prelude = textwrap.dedent(f"""
        import json, sys, time
        from pathlib import Path
        sys.path[:0] = [{str(ROOT / "bench")!r}, {str(ROOT / "src")!r}]
        import jax
        import numpy as np
        from benchlib import data, harness, spec, statements
        assert len(jax.devices()) == 4
        doc = spec.load_benchmark()
        entry = json.loads({json.dumps(_cell_files(tmp, CONFIG["rows"]))!r})
        cell = spec.cell_from_files(entry, doc["end_to_end"],
                                    doc["per_layer"], Path({str(tmp)!r}))
        SEED = {SEED}
    """)
    p = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_four_chip_cell_is_sharded_made_alike_and_correct(tmp_path):
    out = _run_4dev("""
        sliced = []
        sound_rows_at = statements.ref_mod._rows_at

        def rows_at(v, start, size):
            sliced.append((len(v.sharding.device_set), v.shape[0]))
            return sound_rows_at(v, start, size)

        statements.ref_mod._rows_at = rows_at
        mesh = harness.cell_mesh(cell)
        cols, table = harness.make_table(cell, SEED, mesh)
        one = data.make_columns(cell.config, SEED)
        res = {"mesh": table.mesh is mesh and table.row_axes == ("data",)}
        for k in one:
            res[k] = {
                "devices": len(cols[k].sharding.device_set),
                "spec": str(cols[k].sharding.spec),
                "in_table": table.columns[k] is cols[k],
                "equal": bool(np.array_equal(np.asarray(one[k]),
                                             np.asarray(cols[k]))),
                "shards": sorted(s.data.shape[0]
                                 for s in cols[k].addressable_shards)}
        # y = x.b + noise is summed over k in an order XLA picks for each
        # program's shapes: the gap to the one-device y, over the f32
        # rounding bound of the dot, k ulps of sum |b_j x_ij| and one of y
        b = jax.random.normal(jax.random.split(data.seed_key(SEED), 4)[0],
                              (cell.config["k"],), jax.numpy.float32)
        x, y1 = np.asarray(one["x"], np.float64), np.asarray(one["y"])
        eps = float(np.finfo(np.float32).eps)
        bound = eps * (x.shape[1] * (np.abs(x) @ np.abs(np.asarray(
            b, np.float64))) + np.abs(y1))
        gap = np.abs(np.asarray(cols["y"], np.float64) - y1)
        res["y_gap_over_bound"] = float(np.max(gap / bound))
        st = statements.make(cell.traffic["statements"][0], cell.config)
        whole = {k: jax.device_put(np.asarray(v), jax.devices()[0])
                 for k, v in cols.items()}
        ref1 = statements.fold_reference([st], whole)[0]
        sliced.clear()
        ref4 = statements.fold_reference([st], cols)[0]
        res["ref_gaps"] = st.gaps(ref4, ref1)
        res["sliced"] = sliced
        del table, cols, one
        run = harness.run_cell(cell, SEED, 0.3, False,
                               t_origin=time.perf_counter())
        res["run"] = {k: run[k] for k in ("correct", "attempted", "failed",
                                          "checks", "device")}
        print(json.dumps(res))
    """, tmp_path)
    assert out["mesh"]
    for k in ("x", "y", "g"):
        assert out[k] == {"devices": 4, "spec": "PartitionSpec('data',)",
                          "in_table": True, "equal": out[k]["equal"],
                          "shards": [5000] * 4}, k
    # x and g are the one-device build's bit for bit; y within the f32
    # rounding of its sum
    assert out["x"]["equal"] and out["g"]["equal"]
    assert out["y_gap_over_bound"] <= 1.0, out["y_gap_over_bound"]
    # the reference over the shards is the reference over the same
    # columns on one device, read in other chunks; no slice it made saw
    # more than one shard
    assert all(v < 1e-12 for v in out["ref_gaps"].values()), out["ref_gaps"]
    assert out["sliced"] and all(d == 1 and n == 5000
                                 for d, n in out["sliced"])
    run = out["run"]
    assert run["correct"], run["checks"]
    assert run["attempted"] >= 1 and run["failed"] == 0
    assert run["device"]["count"] == 4


def test_a_four_chip_cell_without_the_exchange_is_not_correct(tmp_path):
    run = _run_4dev("""
        from repro.core import aggregates

        # each segment's per-group states stay its own: the merge across
        # chips is left out
        aggregates.merge_group_states = lambda agg, ops, states, axes: states
        run = harness.run_cell(cell, SEED, 0.3, False,
                               t_origin=time.perf_counter())
        print(json.dumps({k: run[k] for k in ("correct", "attempted",
                                              "failed", "checks")}))
    """, tmp_path)
    assert run["attempted"] >= 1 and run["failed"] == 0
    assert not run["correct"], run["checks"]
    assert run["checks"]["rows_gap"]["value"] > 0


def test_rows_that_do_not_divide_over_the_chips_raise_before_set_up(
        tmp_path, monkeypatch):
    doc = spec.load_benchmark()
    cell = spec.cell_from_files(_cell_files(tmp_path, 20_001),
                                doc["end_to_end"], doc["per_layer"],
                                tmp_path)

    def never(*a, **kw):
        raise AssertionError("set-up began")

    monkeypatch.setattr(data, "make_columns", never)
    with pytest.raises(ValueError, match="tiny4.group_linregr: 20001 rows"):
        harness.run_cell(cell, SEED, 0.3, False,
                         t_origin=time.perf_counter())
