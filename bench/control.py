#!/usr/bin/env python3
"""Readings that set a cell's limits for ``correct``: whole runs of the
program and of the control, at the cell's own size, one seed after another
in one process.

    python3 bench/control.py --workload fig4_k80.group_linregr \\
        --seeds 1,2,3 --control-seeds 1,2,3 --seconds 1

Each reading is a run of the cell as ``bench/run.py`` makes it (table from
the seed, warm-up, a short window, every answer compared by
``harness.check_answers``), printed as one JSON line with ``correct`` and
the numbers compared; the gaps that have no limit are on the line before
it.  The control is the program with its linregr matmuls switched from
the configuration's HIGHEST to HIGH (three bf16 passes), the step one
precision below: it has to come out not correct.  The benchmark's own
runs do not run this.  Without a TPU it exits 2.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


@contextlib.contextmanager
def program_at_high(mm=None):
    """The program's linregr statistics and solve taken with ``mm``
    (default: ``jnp.matmul`` at ``Precision.HIGH``) instead of its own
    HIGHEST matmul, for the runs inside the block."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from repro.methods import linregr

    sound = linregr._mm
    linregr._mm = mm or partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGH)
    jax.clear_caches()
    try:
        yield
    finally:
        linregr._mm = sound
        jax.clear_caches()


def reading(cell, seed: int, seconds: float, run: str) -> dict:
    from benchlib import harness
    out = harness.run_cell(cell, seed, seconds, False,
                           t_origin=time.perf_counter())
    return {"seed": seed, "run": run, "correct": out["correct"],
            "attempted": out["attempted"], "checks": out["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds for the program's runs")
    ap.add_argument("--control-seeds", required=True,
                    help="comma-separated seeds for the control's runs")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    from benchlib import harness, spec
    cell = spec.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was read", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        print(json.dumps(reading(cell, seed, args.seconds, "program")),
              flush=True)
    with program_at_high():
        for seed in (int(s) for s in args.control_seeds.split(",")):
            print(json.dumps(reading(cell, seed, args.seconds, "control")),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
