"""The one traffic generator: every mix in ``traffic/*.json`` is data for it.

A mix names its statements (``statements``: a list of ``{"kind": ...,
parameters}``) and how often each is issued (``weights``, or a Zipf
``popularity`` over the list).  One analyst issues them back to back, each
when the last one returned (a closed loop).

Every seed gets the same set of statements in another order, so that
seeds change the order of the work and not its amount.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) & (2**64 - 1))


def mix_counts(traffic: dict, n: int) -> np.ndarray:
    """How many of ``n`` statements are of each catalog entry."""
    m = len(traffic["statements"])
    if "popularity" in traffic:
        w = 1.0 / np.arange(1, m + 1, dtype=np.float64) ** float(
            traffic["popularity"])
    else:
        w = np.asarray(traffic.get("weights", [1.0] * m), np.float64)
    w = w / w.sum()
    counts = np.floor(w * n).astype(np.int64)
    # largest remainders take the rows that flooring left over
    rest = n - counts.sum()
    counts[np.argsort(-(w * n - counts), kind="stable")[:rest]] += 1
    return counts


def statement_order(traffic: dict, n: int, seed: int) -> np.ndarray:
    """Catalog index of each of ``n`` statements, in issue order."""
    counts = mix_counts(traffic, n)
    order = np.repeat(np.arange(len(counts)), counts)
    return _rng(seed).permutation(order)
