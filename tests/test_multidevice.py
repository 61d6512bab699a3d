"""Multi-device integration tests.

These run in SUBPROCESSES with ``--xla_force_host_platform_device_count=8``
so the main test session keeps seeing 1 device (per the dry-run-only
device-forcing rule).  They verify real cross-device semantics: sharded
UDA == local UDA, split-K decode across a real model axis, compressed
psum, and a sharded train step.
"""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess-per-test, 8 forced host devices

ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_py(code: str, timeout=420):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=ENV, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_uda_8dev():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import fit, run_local, run_sharded, \\
            synthetic_regression_table
        from repro.methods.linregr import LinregrAggregate
        from repro.methods.logregr import IRLSTask
        tbl, _ = synthetic_regression_table(jax.random.PRNGKey(0), 8192, 16)
        from repro.core.compat import make_mesh
        mesh = make_mesh((8,), ("data",))
        local = run_local(LinregrAggregate(), tbl)
        dist = tbl.distribute(mesh)
        sharded = run_sharded(LinregrAggregate(), dist, block_size=256)
        np.testing.assert_allclose(np.asarray(local.coef),
                                   np.asarray(sharded.coef),
                                   rtol=1e-4, atol=1e-5)
        # mask= chunks alongside the rows: fold-level base filter parity
        mask = jnp.arange(tbl.n_rows) % 3 == 0
        lm = run_local(LinregrAggregate(), tbl, mask=mask)
        sm = run_sharded(LinregrAggregate(), dist, mask=mask,
                         block_size=256)
        np.testing.assert_allclose(np.asarray(lm.coef),
                                   np.asarray(sm.coef),
                                   rtol=1e-4, atol=1e-5)
        assert float(sm.num_rows) == float(mask.sum())
        # Bernoulli labels on the logit (not the sign of a low-noise
        # linear signal, which is near-separable and sends the IRLS
        # coefficients off toward infinity)
        u = jax.random.uniform(jax.random.PRNGKey(1), (tbl.n_rows,))
        y = (u < jax.nn.sigmoid(tbl["y"])).astype(jnp.float32)
        ctbl = tbl.with_column("y", y)
        fl = fit(IRLSTask(), ctbl, max_iters=20, mask=mask)
        fs = fit(IRLSTask(), ctbl.distribute(mesh), max_iters=20,
                 mask=mask, block_size=256)
        assert fl.n_iters == fs.n_iters
        np.testing.assert_allclose(np.asarray(fl.state["beta"]),
                                   np.asarray(fs.state["beta"]),
                                   rtol=1e-4, atol=1e-5)
        print("OK", len(jax.devices()))
    """)
    assert "OK 8" in out


def test_splitk_decode_seq_sharded_8dev():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.decode import make_splitk_decode_attention
        from repro.core.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        b, h, hk, s, dh = 4, 8, 1, 64, 32     # MQA: kv=1 (the hard case)
        k = jax.random.PRNGKey(0)
        q = jax.random.normal(k, (b, 1, h, dh))
        ck = jax.random.normal(jax.random.fold_in(k, 1), (b, s, hk, dh))
        cv = jax.random.normal(jax.random.fold_in(k, 2), (b, s, hk, dh))
        pos = jnp.array([5, 20, 40, 63], jnp.int32)
        attn = make_splitk_decode_attention(mesh, batch_axes=("data",))
        ck_sh = jax.device_put(ck, NamedSharding(
            mesh, P("data", "model", None, None)))
        cv_sh = jax.device_put(cv, NamedSharding(
            mesh, P("data", "model", None, None)))
        out = attn(q, ck_sh, cv_sh, pos)
        qg = q.reshape(b, hk, h // hk, dh)
        logits = jnp.einsum("bhgd,bkhd->bhgk", qg, ck) / (dh ** 0.5)
        valid = jnp.arange(s)[None, :] <= pos[:, None]
        logits = jnp.where(valid[:, None, None], logits, -1e30)
        w = jax.nn.softmax(logits, -1)
        ref = jnp.einsum("bhgk,bkhd->bhgd", w, cv).reshape(b, 1, h, dh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        print("SPLITK-OK")
    """)
    assert "SPLITK-OK" in out


def test_sharded_grouped_uda_8dev():
    """run_grouped(mesh=) across 8 devices is BIT-IDENTICAL to the local
    segment fold for exact-state aggregates (dyadic linregr, integer
    Count-Min), and the sharded masked fallback serves generic merges."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import Table, run_grouped
        from repro.core.compat import make_mesh
        from repro.methods.linregr import LinregrAggregate
        from repro.methods.sketches import CountMinAggregate
        mesh = make_mesh((8,), ("data",))
        k = jax.random.PRNGKey(0)
        n, d, G = 4001, 4, 7
        kx, ky, kg, ki = jax.random.split(k, 4)
        x = jnp.round(jax.random.normal(kx, (n, d)) * 8) / 8
        y = jnp.round(jax.random.normal(ky, (n,)) * 8) / 8
        g = jax.random.randint(kg, (n,), 0, G - 2)   # two groups empty
        item = jax.random.randint(ki, (n,), 0, 500)
        tbl = Table.from_columns({"x": x, "y": y, "g": g, "item": item})
        for agg in (LinregrAggregate(), CountMinAggregate(4, 256)):
            loc = run_grouped(agg, tbl, "g", G, method="segment",
                              block_size=128)
            sh = run_grouped(agg, tbl, "g", G, method="segment",
                             block_size=128, mesh=mesh)
            for a, b in zip(jax.tree.leaves(loc), jax.tree.leaves(sh)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # generic-merge fallback takes the sharded masked path
        from repro.methods.kmeans import GumbelPickAggregate
        t2 = Table.from_columns({
            "x": x, "d2": jnp.ones((n,)),
            "__row__": jnp.arange(n, dtype=jnp.int32), "g": g})
        agg = GumbelPickAggregate(jax.random.PRNGKey(1), d)
        o_sh = run_grouped(agg, t2, "g", G, mesh=mesh)
        o_lo = run_grouped(agg, t2, "g", G, mesh=None)
        for a, b in zip(jax.tree.leaves(o_sh), jax.tree.leaves(o_lo)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        print("GROUPED-OK", len(jax.devices()))
    """)
    assert "GROUPED-OK 8" in out


def test_sharded_fit_grouped_8dev():
    """fit_grouped(mesh=) runs the whole frozen-group loop in one
    shard_map program with per-group n_iters parity vs local: exact on a
    deterministic countdown task, and matching IRLS models/iteration
    counts on a real grouped logistic fit."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import IterativeTask, Table, fit_grouped
        from repro.core.aggregates import Aggregate, MERGE_SUM
        from repro.core.compat import make_mesh
        from repro.methods.logregr import IRLSTask
        mesh = make_mesh((8,), ("data",))

        class MeanAgg(Aggregate):
            merge_ops = MERGE_SUM
            def init(self, block):
                return {"s": jnp.zeros(()), "n": jnp.zeros(())}
            def transition(self, state, block, mask):
                m = mask.astype(jnp.float32)
                return {"s": state["s"] + jnp.sum(block["k"] * m),
                        "n": state["n"] + jnp.sum(m)}
            def final(self, s):
                return s["s"] / jnp.maximum(s["n"], 1.0)

        class Countdown(IterativeTask):
            def init_state(self, columns):
                return {"it": jnp.zeros(())}
            def make_aggregate(self, state):
                return MeanAgg()
            def update(self, state, out):
                return {"it": state["it"] + 1.0}
            def metric(self, prev, new, out):
                return out - new["it"]

        # group i's mean(k) == i + 1 exactly -> converges after i+1 rounds
        G, per = 6, 600
        g = jnp.repeat(jnp.arange(G, dtype=jnp.int32), per)
        tbl = Table.from_columns({"k": (g + 1).astype(jnp.float32),
                                  "g": g})
        loc = fit_grouped(Countdown(), tbl, "g", G, max_iters=20, tol=0.5,
                          block_size=64)
        sh = fit_grouped(Countdown(), tbl, "g", G, max_iters=20, tol=0.5,
                         block_size=64, mesh=mesh)
        np.testing.assert_array_equal(loc.n_iters, np.arange(1, G + 1))
        np.testing.assert_array_equal(sh.n_iters, loc.n_iters)
        np.testing.assert_array_equal(sh.stats["active_rows"],
                                      loc.stats["active_rows"])
        assert sh.stats["sharded"] and not loc.stats["sharded"]

        # real model: grouped IRLS, per-group n_iters + coefficient parity
        k = jax.random.PRNGKey(0)
        n, d, G2 = 4096, 4, 5
        kx, kg, ku = jax.random.split(k, 3)
        x = jnp.round(jax.random.normal(kx, (n, d)) * 8) / 8
        gid = jax.random.randint(kg, (n,), 0, G2)
        b = 1.0 + jnp.arange(G2, dtype=jnp.float32)[:, None] \\
            * jnp.ones((G2, d)) * 0.3
        p = jax.nn.sigmoid(jnp.sum(x * b[gid], -1))
        y = (jax.random.uniform(ku, (n,)) < p).astype(jnp.float32)
        ftbl = Table.from_columns({"x": x, "y": y, "g": gid})
        rl = fit_grouped(IRLSTask(), ftbl, "g", G2, max_iters=30,
                         tol=1e-6, block_size=128)
        rs = fit_grouped(IRLSTask(), ftbl, "g", G2, max_iters=30,
                         tol=1e-6, block_size=128, mesh=mesh)
        np.testing.assert_array_equal(rl.n_iters, rs.n_iters)
        np.testing.assert_allclose(np.asarray(rl.state["beta"]),
                                   np.asarray(rs.state["beta"]),
                                   rtol=1e-4, atol=1e-6)
        print("FITGROUPED-OK", loc.n_iters.tolist(), rl.n_iters.tolist())
    """)
    assert "FITGROUPED-OK" in out


def test_ivm_empty_view_and_sharding_8dev():
    """PR-6 regressions at real device counts: (a) a sharded grouped
    pass over an all-empty view consumes the sentinel-padded block
    layout (every segment owns whole blocks even with 0 real rows);
    (b) derived columns on a distributed table are actually row-sharded
    over the 8 devices, and append re-places the grown table."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.core import Table, run_grouped
        from repro.core.compat import make_mesh
        from repro.methods.linregr import LinregrAggregate
        mesh = make_mesh((8,), ("data",))
        # (a) all ids out of range -> every group empty
        t = Table.from_columns({
            "g": jnp.full((64,), -1, jnp.int32),
            "x": jnp.ones((64, 2)), "y": jnp.ones((64,))})
        view = t.group_by("g", 5)
        cols, valid, bgids = view.sharded_blocks(mesh, block_size=4)
        assert bgids.shape[0] == 8 and bgids.shape[0] % 8 == 0
        assert not bool(valid.any())
        out = run_grouped(LinregrAggregate(), view, mesh=mesh,
                          block_size=4)
        np.testing.assert_array_equal(np.asarray(out.num_rows),
                                      np.zeros(5))
        # (b) sharding invariants across with_column / append
        t2 = Table.from_columns({"a": jnp.arange(64.0)}).distribute(mesh)
        t3 = t2.with_column("b", jnp.arange(64.0) * 2)
        assert isinstance(t3["b"].sharding, NamedSharding)
        assert len(t3["b"].sharding.device_set) == 8
        t3.append({"a": jnp.arange(16.0), "b": jnp.arange(16.0)})
        assert t3.n_rows == 80 and t3.version == 1
        assert len(t3["a"].sharding.device_set) == 8
        print("IVM-OK", len(jax.devices()))
    """)
    assert "IVM-OK 8" in out


def test_compressed_psum_8dev():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum, \\
            init_error_feedback
        from repro.core.compat import make_mesh
        mesh = make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))

        def body(g_shard, key):
            grads = {"w": g_shard[0]}
            err = init_error_feedback(grads)
            out, new_e = compressed_psum(grads, err, key, "pod")
            return out["w"]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P()), out_specs=P("pod"),
            check_vma=False))
        keys = jax.random.PRNGKey(1)
        out = fn(g[:, None], keys)           # (8, 1024): per-shard results
        mean_true = jnp.mean(g, axis=0)
        # every shard's dequantized mean approximates the true mean
        err = float(jnp.max(jnp.abs(out[0] - mean_true)))
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert err < 3 * scale, (err, scale)
        print("COMPRESS-OK")
    """)
    assert "COMPRESS-OK" in out


def test_sharded_train_step_8dev():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import reduced_config
        from repro.data import synthetic_batch
        from repro.train.trainer import (init_train_state, jit_train_step,
                                         make_train_step)
        from repro.distributed.sharding import DEFAULT_RULES
        cfg = reduced_config("qwen3-8b")
        from repro.core.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        state, axes = init_train_state(cfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, base_lr=1e-2, warmup=1, total_steps=50)
        batch = synthetic_batch(cfg, 8, 16, jax.random.PRNGKey(1))
        spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in batch.items()}
        fn = jit_train_step(step, state, axes, spec, mesh, DEFAULT_RULES)
        losses = []
        for _ in range(4):
            state, m = fn(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        assert np.isfinite(losses).all()
        print("TRAIN-OK", [round(l, 3) for l in losses])
    """)
    assert "TRAIN-OK" in out


def test_group_by_view_of_a_distributed_table_stays_distributed_4dev():
    """Each chip holds a quarter of the partitioned copy, not all of it."""
    run_py("""
        import jax, numpy as np
        from repro.core import Table
        from repro.core.compat import make_mesh
        from repro.distributed.sharding import row_pspec
        mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
        t = Table.from_columns({"x": np.arange(64.0).reshape(32, 2),
                                "y": np.arange(32.0),
                                "g": np.arange(32) % 3}).distribute(mesh)
        view = t.group_by("g")
        for name, col in view.table.columns.items():
            assert col.sharding.spec == row_pspec(("data",), col.ndim), name
            assert [s.data.shape[0] for s in col.addressable_shards] \
                == [8] * 4, name
    """)


def test_chip_smoke_four_chip_phases_4dev():
    """``chip_smoke.py --four-chips`` phases at a tiny size on 4 of the
    forced host devices: sharded grouped scan, fit and joined scan, each
    against the same statements on one device."""
    run_py("""
        import jax
        import chip_smoke as cs
        size = cs.Size(rows=4096, k=8, groups=8, stores=32, items=1000,
                       width=256, append_rows=64, sessions=3, chunk=1000)
        checks = cs.Checks()
        cs.run_four_chips(size, 0, checks, jax.devices()[:4])
        assert checks.failed == [], checks.failed
    """)
