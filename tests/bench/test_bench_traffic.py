"""The traffic generator: the same seed gives the same traffic, and every
seed the same amount of work in another order."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from benchlib import spec, traffic  # noqa: E402

MIX = {"popularity": 1.1, "statements": [{"kind": k} for k in "abcdefg"]}
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_traffic(seed):
    np.testing.assert_array_equal(traffic.statement_order(MIX, 500, seed),
                                  traffic.statement_order(MIX, 500, seed))


def test_seeds_change_the_order_not_the_amount():
    orders = [traffic.statement_order(MIX, 500, s) for s in SEEDS]
    for o in orders[1:]:
        assert not np.array_equal(o, orders[0])
        np.testing.assert_array_equal(np.sort(o), np.sort(orders[0]))


def test_mix_counts_follow_weights_and_popularity():
    counts = traffic.mix_counts(MIX, 1000)
    assert counts.sum() == 1000
    assert np.all(np.diff(counts) <= 0)          # Zipf: most popular first
    w = {"statements": [{"kind": "a"}, {"kind": "b"}], "weights": [3, 1]}
    assert traffic.mix_counts(w, 10).tolist() == [8, 2]
    one = {"statements": [{"kind": "a"}]}
    assert traffic.statement_order(one, 4, 1).tolist() == [0, 0, 0, 0]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cells_mix_names_known_statement_kinds(cell):
    mix = spec.load_cell(cell).traffic
    assert mix["statements"]
    assert all(spec.kind(p["kind"]).Statement for p in mix["statements"])
