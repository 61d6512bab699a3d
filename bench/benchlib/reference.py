"""The plain reference: ordinary least squares in float64 on the host.

It imports nothing of the program.  It reads the benchmark's own columns
(made by :mod:`benchlib.data` from the seed) from the device in row chunks,
folds per-group float64 sufficient statistics, and solves the normal
equations with numpy -- the semantics of ``chip_smoke.py``'s
``ref_ols_stats`` / ``ref_ols``, kept here so that no later change to the
program moves the yardstick.
"""

from __future__ import annotations

from functools import partial

import jax
import numpy as np

CHUNK = 1 << 19                 # rows per host step


@partial(jax.jit, static_argnames=("size",))
def _rows_at(v, start, size):
    return jax.lax.dynamic_slice_in_dim(v, start, size)


def host_chunks(cols: dict, names, chunk: int = CHUNK):
    """``{name: numpy rows}`` for consecutive row chunks of ``cols``.
    One compiled slice per column shape: the start is an argument."""
    n = cols[names[0]].shape[0]
    size = min(chunk, n)
    for a in range(0, n, size):
        start = min(a, n - size)            # the last chunk is clipped
        skip = a - start                    # rows an earlier chunk had
        yield {k: np.asarray(_rows_at(cols[k], start, size))[skip:]
               for k in names}


def _group_slices(g: np.ndarray, groups: int):
    order = np.argsort(g, kind="stable")
    bounds = np.searchsorted(g[order], np.arange(groups + 1))
    for gi in range(groups):
        if bounds[gi + 1] > bounds[gi]:
            yield gi, order[bounds[gi]:bounds[gi + 1]]


def ols_chunk(c: dict, k: int, groups: int = 1,
              group_col: str | None = None) -> dict:
    """Per-group float64 sufficient statistics of y on x over one chunk."""
    s = {"xtx": np.zeros((groups, k, k)), "xty": np.zeros((groups, k)),
         "y_sum": np.zeros(groups), "y_sq": np.zeros(groups),
         "n": np.zeros(groups)}
    x = c["x"].astype(np.float64)
    y = c["y"].astype(np.float64)
    parts = (_group_slices(c[group_col], groups) if group_col
             else [(0, slice(None))])
    for gi, idx in parts:
        xc, yc = x[idx], y[idx]
        s["xtx"][gi] += xc.T @ xc
        s["xty"][gi] += xc.T @ yc
        s["y_sum"][gi] += yc.sum()
        s["y_sq"][gi] += yc @ yc
        s["n"][gi] += len(yc)
    return s


def add(a: dict, b: dict) -> dict:
    return {k: a[k] + b[k] for k in a}


def ols(s: dict) -> dict:
    """Coefficients, R^2 and standard errors from stacked statistics."""
    xtx, xty, n = s["xtx"], s["xty"], s["n"]
    k = xtx.shape[-1]
    inv = np.linalg.inv(xtx)
    coef = np.einsum("gij,gj->gi", inv, xty)
    sse = s["y_sq"] - np.einsum("gk,gk->g", coef, xty)
    tss = s["y_sq"] - s["y_sum"] ** 2 / n
    sigma2 = sse / np.maximum(n - k, 1.0)
    std_err = np.sqrt(np.einsum("gii->gi", inv) * sigma2[:, None])
    return {"coef": coef, "r2": 1.0 - sse / tss, "std_err": std_err,
            "n": n}


def ols_gaps(got: dict, ref: dict) -> dict:
    """How far an answer lies from the reference's, per number compared:

    - ``rows``: largest difference of a group's row count (exact);
    - ``coef``: largest coefficient gap over the group's largest
      reference coefficient, over groups;
    - ``sse``: largest relative gap of the unexplained share 1 - R^2;
    - ``std_err``: largest relative gap of a standard error;
    - ``scale``: the coefficients' systematic scale error, the least-
      squares slope of (answer - reference) on the reference over every
      coefficient of every group: rounding that is as often up as down
      averages out of it, a bias that shrinks or grows every coefficient
      (products that drop their low bits) does not.
    """
    g = {k: np.asarray(got[k], np.float64).reshape(ref[k].shape)
         for k in ("coef", "r2", "std_err", "n")}
    scale = np.max(np.abs(ref["coef"]), axis=-1, keepdims=True)
    unexplained = 1.0 - ref["r2"]
    out = {
        "rows": float(np.max(np.abs(g["n"] - ref["n"]))),
        "coef": float(np.max(np.abs(g["coef"] - ref["coef"]) / scale)),
        "sse": float(np.max(np.abs((1.0 - g["r2"]) - unexplained)
                            / unexplained)),
        "std_err": float(np.max(np.abs(g["std_err"] - ref["std_err"])
                                / ref["std_err"])),
        "scale": float(abs(np.sum((g["coef"] - ref["coef"]) * ref["coef"]))
                       / np.sum(ref["coef"] ** 2)),
    }
    # a NaN answer is as far off as an answer can be
    return {k: (float("inf") if not np.isfinite(v) else v)
            for k, v in out.items()}
