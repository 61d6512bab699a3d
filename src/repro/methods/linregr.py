"""Ordinary least squares — the paper's single-pass UDA example (§4.1).

State: ``X^T X`` (symmetric, accumulated as a blocked rank-TILE MXU update —
see kernels/xtx for the Pallas hot loop), ``X^T y``, and scalar moments of
``y``.  merge = sum (associative ⇒ data parallelism "for free", §4.1);
final = pseudo-inverse solve + the output statistics MADlib's linregr
returns (R², std errors, t-stats, p-values, condition number — Listing 2
computes the condition number of ``X^T X``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..core.aggregates import Aggregate, MERGE_SUM
from ..core.iterative import IterativeTask
from ..core.join import Join
from ..core.plan import GroupedScanAgg, JoinedGroupedScanAgg, ScanAgg, \
    execute
from ..core.table import Table
from ..kernels.registry import dispatch, resolve_impl

# Sufficient statistics and the solve run at full f32 precision: a TPU's
# DEFAULT matmul precision rounds f32 operands to bf16, which moves the
# coefficients by about 1e-3 relative.
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass
class LinregrResult:
    coef: jax.Array
    r2: jax.Array
    std_err: jax.Array
    t_stats: jax.Array
    p_values: jax.Array
    condition_no: jax.Array
    num_rows: jax.Array


class LinregrAggregate(Aggregate):
    """(init, transition, merge, final) for OLS.  ``use_kernel`` routes the
    inner X^T X update through the kernel registry: True = backend-aware
    auto dispatch (compiled Pallas on TPU, jnp ref elsewhere); "pallas" /
    "ref" force an implementation."""

    merge_ops = MERGE_SUM
    # grouped hot path: the whole segment fold as one fused Pallas grid
    # loop (kernels/segment_fold), dispatched by name via the registry
    segment_kernel = "segment_linregr"
    # planner calibration bucket (measured cost tables key on this)
    cost_class = "xtx"

    def __init__(self, use_kernel: bool | str = False):
        self.kernel_impl = resolve_impl(use_kernel)

    def cache_key(self):
        return ("linregr", self.kernel_impl)

    def segment_kernel_args(self, columns, valid, block_gids, num_groups):
        return ((columns["x"], columns["y"], valid, block_gids),
                {"num_groups": num_groups})

    def init(self, block):
        d = block["x"].shape[-1]
        f = block["x"].dtype
        return {
            "xtx": jnp.zeros((d, d), f),
            "xty": jnp.zeros((d,), f),
            "y_sum": jnp.zeros((), f),
            "y_sq": jnp.zeros((), f),
            "n": jnp.zeros((), jnp.float32),
        }

    def transition(self, state, block, mask):
        x = block["x"] * mask[:, None].astype(block["x"].dtype)
        y = block["y"] * mask.astype(block["y"].dtype)
        if self.kernel_impl is not None:
            xtx, xty = dispatch("xtx", x, y, impl=self.kernel_impl)
        else:
            # The paper's v0.3 lesson: express the rank-1 updates as one
            # rank-B update (k,B)@(B,k) — systolic-array native.
            xtx = _mm(x.T, x)
            xty = _mm(x.T, y)
        return {
            "xtx": state["xtx"] + xtx,
            "xty": state["xty"] + xty,
            "y_sum": state["y_sum"] + jnp.sum(y),
            "y_sq": state["y_sq"] + jnp.sum(y * y),
            "n": state["n"] + jnp.sum(mask.astype(jnp.float32)),
        }

    def final(self, s):
        xtx, xty, n = s["xtx"], s["xty"], s["n"]
        d = xtx.shape[0]
        # SymmetricPositiveDefiniteEigenDecomposition + pseudo-inverse
        # (Listing 2), via eigh.
        w, v = jnp.linalg.eigh(xtx)
        eps = jnp.finfo(xtx.dtype).eps * d * jnp.max(jnp.abs(w))
        inv_w = jnp.where(w > eps, 1.0 / w, 0.0)
        pinv = _mm(v * inv_w, v.T)
        coef = _mm(pinv, xty)
        cond = jnp.max(jnp.abs(w)) / jnp.maximum(jnp.min(jnp.abs(w)), 1e-30)

        # coef·XᵀX·coef == coef·Xᵀy for the pseudo-inverse solution, so
        # SSE = yᵀy - coef·Xᵀy: one f32 cancellation instead of two, which
        # keeps sse (and r2, std_err) stable to rounding of the terms
        sse = s["y_sq"] - _mm(coef, xty)
        tss = s["y_sq"] - (s["y_sum"] ** 2) / n
        r2 = 1.0 - sse / jnp.maximum(tss, 1e-30)
        dof = jnp.maximum(n - d, 1.0)
        sigma2 = sse / dof
        std_err = jnp.sqrt(jnp.maximum(jnp.diag(pinv) * sigma2, 0.0))
        t = coef / jnp.maximum(std_err, 1e-30)
        p = 2.0 * (1.0 - jax.scipy.stats.norm.cdf(jnp.abs(t)))
        return LinregrResult(coef, r2, std_err, t, p, cond, n)


jax.tree_util.register_pytree_node(
    LinregrResult,
    lambda r: ((r.coef, r.r2, r.std_err, r.t_stats, r.p_values,
                r.condition_no, r.num_rows), None),
    lambda _, c: LinregrResult(*c),
)


class LinregrTask(IterativeTask):
    """OLS as a degenerate (single-pass, counted) executor task — which is
    exactly what buys it ``GROUP BY`` fitting via :func:`fit_grouped`:
    the paper's grouped linregr (§4.1) is ``linregr_grouped`` below."""

    def __init__(self, use_kernel: bool | str = False):
        self.use_kernel = use_kernel

    def init_state(self, columns):
        return jnp.zeros(())  # stateless: everything lives in the pass

    def make_aggregate(self, state):
        return LinregrAggregate(use_kernel=self.use_kernel)

    def update(self, state, out):
        return state

    def finalize(self, state, out):
        return out


def linregr(table: Table, *, x_col: str = "x", y_col: str = "y",
            block_size: int | None = None, use_kernel: bool | str = False
            ) -> LinregrResult:
    """``SELECT (linregr(y, x)).* FROM data`` — one ``ScanAgg`` statement;
    the planner picks local vs sharded from the table's distribution, and
    batching it with other one-pass statistics (via ``Session``) shares
    the scan."""
    return execute(ScanAgg(LinregrAggregate(use_kernel), table,
                           columns={"x": x_col, "y": y_col},
                           block_size=block_size, label="linregr"))


def linregr_grouped(table: Table, key_col: str,
                    num_groups: int | None = None, *, x_col: str = "x",
                    y_col: str = "y", block_size: int | None = None,
                    use_kernel: bool | str = False,
                    mesh=None) -> LinregrResult:
    """``SELECT g, (linregr(y, x)).* FROM data GROUP BY g`` — one model per
    group in a shared scan; every result field has a leading group axis.
    ``mesh`` (defaulting to the table's) runs the scan on the sharded
    grouped engine; the partitioning sort is shared with every other
    grouped statement over the same (table, key) via the group_by memo."""
    return execute(GroupedScanAgg(
        LinregrAggregate(use_kernel), table, key_col, num_groups,
        columns={"x": x_col, "y": y_col}, block_size=block_size,
        mesh=mesh, label="linregr_grouped"))


def linregr_joined(fact: Table, dim: Table, *, fact_key: str,
                   dim_key: str, attr_col: str,
                   on_missing: str = "error",
                   num_groups: int | None = None, x_col: str = "x",
                   y_col: str = "y", block_size: int | None = None,
                   use_kernel: bool | str = False, mesh=None
                   ) -> LinregrResult:
    """``SELECT dim.attr, (linregr(y, x)).* FROM fact JOIN dim ON
    fact.fk = dim.key GROUP BY dim.attr`` — one model per dimension
    attribute, as ONE joined-grouped statement.  The join resolves
    device-side through the :class:`~repro.core.join.Join` node (sort-
    merge against the memoized dimension key sort; the dimension's
    columns are never gathered onto fact rows) and the scan runs on the
    unchanged grouped core; batched with other statements over the same
    star triple it fuses into one pass."""
    return execute(JoinedGroupedScanAgg(
        LinregrAggregate(use_kernel),
        Join(fact, dim, fact_key, dim_key, attr_col,
             on_missing=on_missing),
        num_groups, columns={"x": x_col, "y": y_col},
        block_size=block_size, mesh=mesh, label="linregr_joined"))
