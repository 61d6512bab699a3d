#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload fig4_k80.group_linregr --seed 7 \\
        --seconds 40 --trace 0

Loads the cell named in ``BENCHMARK.json`` (its files under ``bench/``),
makes its table on the device from ``--seed``, warms up, measures for
``--seconds``, checks every answer of the window against the float64
reference, and prints one JSON object as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import os
import time

T_ORIGIN = time.perf_counter()


def _process_age_s() -> float:
    """Seconds since this process started, where Linux says so."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_ORIGIN -= _process_age_s()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from benchlib import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s). "
              "Nothing was measured.", file=sys.stderr)
        return 2

    from benchlib import harness
    harness.use_compile_cache()
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_origin=T_ORIGIN)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
