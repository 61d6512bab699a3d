"""``chip_smoke.py`` at a tiny size on the CPU.

The script's phases (Session batch, GROUP BY scan and fit, living view,
analytics server, forced Pallas kernels, star join) run here against the
same numpy references as on the chip, with the kernels in interpret
mode; the four-chip phases run in ``test_multidevice.py``.  ``main``
itself must refuse to run anywhere but on a TPU.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

TINY = dict(rows=4096, k=8, groups=8, stores=32, items=1000, width=256,
            append_rows=64, sessions=3, chunk=1000)


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses resolve through here
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_match_the_references(chip_smoke):
    checks = chip_smoke.Checks()
    chip_smoke.run_one_chip(chip_smoke.Size(**TINY), 0, checks)
    assert checks.failed == []


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert "no TPU found" in out.err
    assert out.out == ""
