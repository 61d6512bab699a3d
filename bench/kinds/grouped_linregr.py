"""``SELECT g, (linregr(y, x)).* FROM t GROUP BY g``: grouped least squares
through ``Session.grouped_scan``, its float64 reference and its work."""

from __future__ import annotations

import numpy as np

from benchlib import reference as ref_mod, statements


class Statement:
    """One grouped linregr statement over the configuration's table."""

    def __init__(self, params: dict, cfg: dict):
        self.kind = params["kind"]
        self.group_col = params.get("group_col", "g")
        self.groups = int(cfg["groups"])
        self.k = int(cfg["k"])
        self.columns = ["x", "y", self.group_col]

    def issue(self, session, table):
        from repro.methods.linregr import LinregrAggregate
        return session.grouped_scan(LinregrAggregate(), table,
                                    self.group_col, self.groups,
                                    columns={"x": "x", "y": "y"})

    @staticmethod
    def answer(res) -> dict:
        return {"coef": np.asarray(res.coef), "r2": np.asarray(res.r2),
                "std_err": np.asarray(res.std_err),
                "n": np.asarray(res.num_rows)}

    def ref_state(self, chunk: dict) -> dict:
        return ref_mod.ols_chunk(chunk, self.k, self.groups, self.group_col)

    ref_merge = staticmethod(ref_mod.add)
    ref_final = staticmethod(ref_mod.ols)
    gaps = staticmethod(ref_mod.ols_gaps)

    def reference(self, cols: dict) -> dict:
        """The reference answer for the whole of ``cols``."""
        return statements.fold_reference([self], cols)[0]


def flops(rows: int, k: int) -> float:
    """X^T X and X^T y over ``rows`` rows of ``k`` variables."""
    return 2.0 * rows * k * k + 2.0 * rows * k


def bytes(rows: int, k: int, itemsize: int = 4) -> float:  # noqa: A001
    """x and y read once."""
    return float(rows) * (k + 1) * itemsize
