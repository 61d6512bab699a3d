"""Shared-scan profile benchmark — the §4.1 data-movement argument, one
level up.

MADlib's ``profile`` computes every column's statistics in ONE table
scan; the sequential baseline here re-scans the table once per aggregate
(one ProfileAggregate pass + one FM pass per integer column — exactly
what ``profile`` did before FusedAggregate).  We report, for growing
column counts, the number of data passes each strategy executes (counted
by wrapping the top-level transition) and the measured wall time.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import Table, run_local
from repro.core.aggregates import FusedAggregate
from repro.core.templates import ProfileAggregate
from repro.methods.profile import profile, profile_aggregates
from repro.methods.sketches import FMAggregate


def _timeit(fn, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


class _CountingFused(FusedAggregate):
    """Counts top-level transition invocations == data passes executed."""

    passes = 0

    def transition(self, state, block, mask):
        _CountingFused.passes += 1
        return super().transition(state, block, mask)


def _make_table(key, rows, n_int_cols):
    cols = {"f0": jax.random.normal(key, (rows,)),
            "f1": jax.random.normal(jax.random.fold_in(key, 1), (rows,))}
    for i in range(n_int_cols):
        cols[f"i{i}"] = jax.random.randint(
            jax.random.fold_in(key, 100 + i), (rows,), 0, 5000)
    return Table.from_columns(cols)


def _sequential_profile(table, block_size):
    """The pre-FusedAggregate dataflow: one scan per aggregate."""
    out = dict(run_local(ProfileAggregate(), table, block_size=block_size))
    for name, col in table.columns.items():
        if jnp.issubdtype(col.dtype, jnp.integer) and col.ndim == 1:
            t = Table({"item": col})
            est = run_local(FMAggregate(item_col="item"), t,
                            block_size=block_size)
            out[name] = dict(out[name], approx_distinct=est)
    return out


def run(rows: int = 100_000, reps: int = 3):
    key = jax.random.PRNGKey(0)
    results = []
    block_size = 8192
    for n_int in (1, 4, 8):
        tbl = _make_table(key, rows, n_int)

        # -- pass counts (trace-time; independent of wall clock) ----------
        _CountingFused.passes = 0
        run_local(_CountingFused(profile_aggregates(
            tbl, distinct_counts=True)), tbl, block_size=None)
        fused_passes = _CountingFused.passes
        seq_passes = 1 + n_int               # stats scan + one FM per col

        # -- wall time ----------------------------------------------------
        dt_seq = _timeit(lambda: _sequential_profile(tbl, block_size),
                         reps=reps)
        dt_fused = _timeit(lambda: profile(tbl, distinct_counts=True,
                                           block_size=block_size), reps=reps)
        results.append((
            f"profile_seq_cols{n_int}_n{rows}", dt_seq * 1e6,
            f"passes={seq_passes}"))
        results.append((
            f"profile_fused_cols{n_int}_n{rows}", dt_fused * 1e6,
            f"passes={fused_passes}_speedup={dt_seq / dt_fused:.2f}x"))
    return results


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    for name, us, extra in run():
        print(f"{name},{us:.1f},{extra}")
