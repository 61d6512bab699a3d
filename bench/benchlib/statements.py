"""Statement kinds a traffic mix can name, each a file ``kinds/<kind>.py``.

A kind's file holds ``Statement(params, cfg)``: ``issue`` sends one
statement through the ``Session`` sugar of the system under test and
``answer`` turns its answer into numpy; the reference is a fold over host
chunks of the benchmark's own columns (``ref_state`` per chunk,
``ref_merge`` across chunks, ``ref_final``), and ``gaps`` says how far an
answer lies from it, per number compared.  Beside the class,
``flops(rows, k)`` and ``bytes(rows, k)`` give the statement's work by its
shapes (:mod:`benchlib.roofline`).  A new kind is a new file.
"""

from __future__ import annotations

from . import reference as ref_mod, spec


def make(params: dict, cfg: dict):
    """The statement ``params`` describes, of the kind ``params["kind"]``,
    over the table of ``cfg``."""
    return spec.kind(params["kind"]).Statement(params, cfg)


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
