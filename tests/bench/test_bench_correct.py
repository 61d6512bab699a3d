"""``correct`` separates sound runs from broken ones.

A whole run of each cell of ``BENCHMARK.json`` is driven on the CPU at a
small size, past the harness's look for a chip, with the timed path sound
and then broken underneath in each way the cell can break: a fold step
that returns its state unchanged, half of each batch of rows left out,
and an answer altered where it is produced.  (No cell of
``BENCHMARK.json`` spans chips yet: the exchange between chips left out
is driven on a four-chip cell made of files alone, in
``test_bench_chips.py``.)  The control -- the program with its linregr
matmuls one precision step down, at HIGH -- must come out not correct
through the same comparison.
"""

from __future__ import annotations

import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

import control  # noqa: E402
from benchlib import data, harness, spec, statements  # noqa: E402
from repro.methods import linregr as linregr_mod  # noqa: E402

# rows per cell: enough for every group to outnumber k many times
ROWS = {"fig4_k80.group_linregr": 20_000}
CELLS = sorted(ROWS)


def test_every_cell_is_driven_here():
    assert {w["name"] for w in spec.load_benchmark()["workloads"]} == set(
        ROWS)


def _run(name: str) -> dict:
    cell = spec.load_cell(name)
    return harness.run_cell(cell, 2**31 + 3, 0.3, False,
                            t_origin=time.perf_counter(), rows=ROWS[name])


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(linregr_mod.LinregrAggregate, "transition",
                        lambda self, state, block, mask: state)


def _half_batch(monkeypatch):
    sound = linregr_mod.LinregrAggregate.transition

    def half(self, state, block, mask):
        keep = jnp.arange(mask.shape[0]) % 2 == 0
        return sound(self, state, block, mask & keep)

    monkeypatch.setattr(linregr_mod.LinregrAggregate, "transition", half)


def _answer_altered(monkeypatch):
    sound = linregr_mod.LinregrAggregate.final

    def altered(self, s):
        r = sound(self, s)
        r.coef = r.coef * (1.0 + 1e-3)
        return r

    monkeypatch.setattr(linregr_mod.LinregrAggregate, "final", altered)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(name)
    assert not out["correct"], out["checks"]


def _bf16x3_matmul(a, b):
    """``a @ b`` as a TPU takes it at ``Precision.HIGH``: every f32 operand
    split into a bf16 high part and a bf16 low part, and the product
    summed from three bf16 products (the low parts' product dropped).
    The CPU takes f32 matmuls whole at any precision, so the control is
    planted here as this."""
    def split(v):
        hi = v.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (v - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    """The program at HIGH reads over the cell's limits through the
    comparison a run makes (on the chip at the cell's own size, PERF.md
    gives the readings; here at the test's size)."""
    with control.program_at_high(_bf16x3_matmul):
        out = _run(name)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert not out["correct"], out["checks"]
    assert _run(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_the_reference_reads_nought_against_itself(name):
    cell = spec.load_cell(name)
    st = statements.make(cell.traffic["statements"][0], cell.config)
    cols = data.make_columns(cell.config, 2**31 + 3, ROWS[name])
    ref = st.reference(cols)
    assert all(v == 0 for v in st.gaps(ref, ref).values())
    assert ref["n"].sum() == ROWS[name]
