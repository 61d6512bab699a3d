"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own, named after it:

    bench/configs/<config>.json      sizes of the table, and what was assumed
    bench/traffic/<traffic>.json     the statement mix and how it arrives
    bench/workloads/<cell>.json      the cell's limits for ``correct``
    bench/metrics/<metric>.py        ``read(ctx)``: one per-layer metric
    bench/kinds/<kind>.py            one statement kind a mix can name

so a later change adds a cell, a mix, a metric or a kind by adding files.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parents[2]      # the checkout
BENCH = Path(__file__).resolve().parents[1]     # bench/


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench: Path | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read
    from ``bench`` (default ``root/bench``).  A name that is not in
    ``BENCHMARK.json`` raises ``KeyError``."""
    spec = load_benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    return cell_from_files(cells[name], spec["end_to_end"],
                           spec["per_layer"], bench or root / "bench")


def cell_from_files(entry: dict, end_to_end: list, per_layer: list,
                    bench: Path = BENCH) -> Cell:
    """A cell from its ``workloads`` entry (``name``, ``config``,
    ``traffic``, ``chips``) and the metrics it reports, its files read
    from ``bench``."""
    name = entry["name"]
    read = lambda sub, stem: json.loads(
        (bench / sub / f"{stem}.json").read_text())
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=read("configs", entry["config"]),
        traffic=read("traffic", entry["traffic"]),
        workload=read("workloads", name),
        end_to_end=[m for m in end_to_end if _reports(m, name)],
        per_layer=[m for m in per_layer if _reports(m, name)])


def _load(path: Path, prefix: str):
    mod_name = prefix + "".join(c if c.isalnum() else "_"
                                for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH
                  ) -> Callable[[Any], float | None]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _load(bench / "metrics" / f"{name}.py", "bench_metric_").read


@functools.cache
def kind(name: str, bench: Path = BENCH):
    """The module ``bench/kinds/<name>.py``: ``Statement(params, cfg)``,
    ``flops(rows, k)`` and ``bytes(rows, k)`` (see
    :mod:`benchlib.statements`), loaded once per process."""
    return _load(bench / "kinds" / f"{name}.py", "bench_kind_")
