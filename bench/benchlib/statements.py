"""Statement kinds a traffic mix can name, issued as an analyst issues them.

Each kind issues one statement through the ``Session`` sugar of the system
under test and turns its answer into numpy.  Its reference is a fold over
host chunks of the benchmark's own columns (``ref_state`` per chunk,
``ref_merge`` across chunks, ``ref_final``).  ``gaps`` says how far an
answer lies from the reference, per number compared.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref_mod


class GroupedLinregr:
    """``SELECT g, (linregr(y, x)).* FROM t GROUP BY g``."""

    kind = "grouped_linregr"

    def __init__(self, params: dict, cfg: dict):
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
        return fold_reference([self], cols)[0]


KINDS = {"grouped_linregr": GroupedLinregr}


def make(params: dict, cfg: dict):
    return KINDS[params["kind"]](params, cfg)


def fold_reference(entries, cols: dict) -> list:
    """Each entry's reference answer over the whole of ``cols``, in one
    pass of host chunks."""
    names = sorted({c for e in entries for c in e.columns})
    acc = [None] * len(entries)
    for chunk in ref_mod.host_chunks(cols, names):
        for i, e in enumerate(entries):
            part = e.ref_state(chunk)
            acc[i] = part if acc[i] is None else e.ref_merge(acc[i], part)
    return [e.ref_final(s) for e, s in zip(entries, acc)]
