"""Binary logistic regression — the paper's multipass example (§4.2).

Paper-faithful solver: Newton's method as *iteratively reweighted least
squares*, ``β ← (X^T D X)^{-1} X^T D z`` with ``D = diag(p(1-p))`` and
``z = Xβ + D^{-1}(y - p)``.  Each iteration is one UDA execution
(transition accumulates ``X^T D X`` and ``X^T D z``; merge = sum); the
outer loop is :class:`IRLSTask` under the unified iterative executor
(§3.1.2 driver pattern) — which means IRLS inherits the compiled
``lax.while_loop`` fast path, sharded/streaming execution and per-group
(GROUP BY) fitting (:func:`logregr_grouped`) for free.

Also provided: the §5.1 SGD solver over the same objective, for the
Table-2 benchmark.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..core.aggregates import Aggregate, MERGE_SUM
from ..core.convex import ConvexProgram, sgd as sgd_solver, parallel_sgd
from ..core.iterative import IterativeTask
from ..core.plan import IterativeFit, execute
from ..core.table import Table

# Full f32 precision for the IRLS statistics (a TPU's DEFAULT matmul
# precision rounds f32 operands to bf16).
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass
class LogregrResult:
    coef: jax.Array
    log_likelihood: jax.Array
    std_err: jax.Array
    z_stats: jax.Array
    p_values: jax.Array
    n_iters: int
    converged: bool


class IRLSAggregate(Aggregate):
    """One IRLS round: accumulate X^T D X, X^T D z, and the log-likelihood."""

    merge_ops = MERGE_SUM

    def __init__(self, beta: jax.Array):
        self.beta = beta

    def init(self, block):
        d = block["x"].shape[-1]
        return {
            "xdx": jnp.zeros((d, d)),
            "xdz": jnp.zeros((d,)),
            "ll": jnp.zeros(()),
            "n": jnp.zeros(()),
        }

    def transition(self, state, block, mask):
        x = block["x"]
        y = block["y"]
        m = mask.astype(x.dtype)
        eta = _mm(x, self.beta)
        p = jax.nn.sigmoid(eta)
        w = jnp.maximum(p * (1.0 - p), 1e-10) * m          # D diagonal
        z = eta + (y - p) / jnp.maximum(p * (1.0 - p), 1e-10)
        xw = x * w[:, None]
        ll = jnp.sum(m * (y * eta - jax.nn.softplus(eta)))
        return {
            "xdx": state["xdx"] + _mm(xw.T, x),
            "xdz": state["xdz"] + _mm(xw.T, z),
            "ll": state["ll"] + ll,
            "n": state["n"] + jnp.sum(m),
        }


class IRLSTask(IterativeTask):
    """IRLS as an executor task: state = β; one pass = one IRLSAggregate;
    driver update = the weighted-least-squares solve; metric = relative
    coefficient change; finalize = Wald statistics from the last pass's
    Fisher information."""

    def __init__(self, ridge: float = 1e-8):
        self.ridge = ridge

    def init_state(self, columns):
        return {"beta": jnp.zeros((columns["x"].shape[-1],))}

    def make_aggregate(self, state):
        return IRLSAggregate(state["beta"])

    def update(self, state, out):
        d = out["xdx"].shape[0]
        beta = jnp.linalg.solve(out["xdx"] + self.ridge * jnp.eye(d),
                                out["xdz"])
        return {"beta": beta}

    def metric(self, prev, new, out):
        return jnp.linalg.norm(new["beta"] - prev["beta"]) \
            / (jnp.linalg.norm(prev["beta"]) + 1e-12)

    def finalize(self, state, out):
        # Wald statistics from the final Fisher information (X^T D X)^{-1}.
        beta = state["beta"]
        d = beta.shape[0]
        cov = jnp.linalg.inv(out["xdx"] + 1e-8 * jnp.eye(d))
        se = jnp.sqrt(jnp.maximum(jnp.diag(cov), 0.0))
        z = beta / jnp.maximum(se, 1e-30)
        p = 2.0 * (1.0 - jax.scipy.stats.norm.cdf(jnp.abs(z)))
        return {"coef": beta, "ll": out["ll"], "se": se, "z": z, "p": p}


def _result(res) -> LogregrResult:
    f = res.result
    return LogregrResult(f["coef"], f["ll"], f["se"], f["z"], f["p"],
                         res.n_iters, res.converged)


def logregr(table: Table, *, x_col: str = "x", y_col: str = "y",
            max_iters: int = 30, tol: float = 1e-6,
            block_size: int | None = None, mode: str = "compiled",
            warm_start: jax.Array | None = None) -> LogregrResult:
    """``SELECT * FROM logregr('y', 'x', 'data')`` — IRLS under the
    unified executor (sharded automatically when the table is)."""
    t = Table({"x": table[x_col], "y": table[y_col]}, table.mesh,
              table.row_axes)
    ws = None if warm_start is None else {"beta": jnp.asarray(warm_start)}
    res = execute(IterativeFit(IRLSTask(), t, max_iters=max_iters, tol=tol,
                               block_size=block_size, mode=mode,
                               warm_start=ws, label="logregr"))
    return _result(res)


def logregr_stream(blocks_factory, *, max_iters: int = 30,
                   tol: float = 1e-6) -> LogregrResult:
    """Out-of-core IRLS: each iteration streams the blocks from a fresh
    ``blocks_factory()`` (dicts with "x"/"y") with device-resident state."""
    res = execute(IterativeFit(IRLSTask(), blocks=blocks_factory,
                               max_iters=max_iters, tol=tol,
                               label="logregr_stream"))
    return _result(res)


def logregr_grouped(table: Table, key_col: str,
                    num_groups: int | None = None, *,
                    x_col: str = "x", y_col: str = "y",
                    max_iters: int = 30, tol: float = 1e-6,
                    block_size: int | None = None,
                    mesh=None) -> LogregrResult:
    """One logistic model per group, fit in shared scans
    (``SELECT g, (logregr(y, x)).* FROM data GROUP BY g``).  Every field
    of the result carries a leading group axis; ``n_iters``/``converged``
    are per-group vectors.  ``mesh`` (defaulting to the table's) runs the
    whole frozen-group IRLS loop inside one ``shard_map`` program."""
    t = Table({"x": table[x_col], "y": table[y_col],
               key_col: table[key_col]}, table.mesh, table.row_axes)
    res = execute(IterativeFit(IRLSTask(), t, group_col=key_col,
                               num_groups=num_groups, max_iters=max_iters,
                               tol=tol, block_size=block_size, mesh=mesh,
                               label="logregr_grouped"))
    return _result(res)


# ---------------------------------------------------------------------------
# §5.1 SGD path (Table 2 "Logistic Regression" row).
# ---------------------------------------------------------------------------

def logistic_program(mu: float = 0.0) -> ConvexProgram:
    """Σ log(1 + exp(-y·xᵀw)) with y ∈ {−1,+1} encoded from {0,1}."""

    def loss(params, block, mask):
        sgn = 2.0 * block["y"] - 1.0
        return jnp.sum(jax.nn.softplus(-sgn * (block["x"] @ params))
                       * mask.astype(jnp.float32))

    reg = (lambda p: 0.5 * mu * jnp.sum(p ** 2)) if mu > 0 else None
    return ConvexProgram(loss=loss, regularizer=reg)


def logregr_sgd(table: Table, *, epochs: int = 5, stepsize: float = 0.5,
                batch: int = 128, key=None, mu: float = 0.0) -> jax.Array:
    d = table["x"].shape[-1]
    prog = logistic_program(mu)
    if table.mesh is not None:
        return parallel_sgd(prog, table, jnp.zeros((d,)), stepsize=stepsize,
                            epochs=epochs, batch=batch, key=key)
    return sgd_solver(prog, table, jnp.zeros((d,)), stepsize=stepsize,
                      epochs=epochs, batch=batch, key=key)
