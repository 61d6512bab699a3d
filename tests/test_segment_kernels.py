"""Segment-fold kernel package + registry dispatch semantics.

The grouped-engine-level bit-identity matrix lives in
``test_engine_parity.py``; this file pins the layers underneath it:

* the jnp ref oracles and the (interpret-mode) Pallas kernel bodies
  agree bit-for-bit on the group-aligned layout, including the
  masked-invalid sentinel pad blocks ``sharded_blocks`` emits;
* registry resolve semantics: auto degrades to ref off-TPU or when the
  ``supports`` gate rejects; a forced ``impl="pallas"`` warns once (and
  runs interpret) off-TPU but FAILS LOUDLY on a TPU shape the compiled
  kernel cannot take;
* ``supports`` as a ranker: tuned kwargs from the active calibration
  flow into the pallas impl, explicit caller kwargs win;
* kernel dispatch records the RESOLVED impl on active traces, once per
  physical grouped execution;
* a single-member ``FusedAggregate`` (what the planner builds for a lone
  grouped statement) forwards its member's kernel hook.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table, run_grouped, trace_execution
from repro.core.aggregates import FusedAggregate
from repro.kernels import registry
from repro.kernels.segment_fold import ops as sf_ops, ref as sf_ref
from repro.methods.linregr import LinregrAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate

G = 3
BS = 16


def _layout(n_blocks=6, bs=BS, sentinel=True, seed=0):
    """A hand-built group-aligned layout: ``n_blocks`` blocks of ``bs``
    rows, some validity padding, and (optionally) a trailing sentinel pad
    block carrying gid == G — exactly what ``sharded_blocks`` emits."""
    rng = np.random.default_rng(seed)
    gids = rng.integers(0, G, size=n_blocks).astype(np.int32)
    if sentinel:
        gids = np.concatenate([gids, np.array([G], np.int32)])
    n2 = len(gids) * bs
    valid = rng.random(n2) < 0.8
    if sentinel:  # sentinel rows are garbage; the gid guard must drop them
        valid[-bs:] = rng.random(bs) < 0.5
    x = (rng.integers(-8, 8, size=(n2, 3)) / 4.0).astype(np.float32)
    y = (rng.integers(-8, 8, size=(n2,)) / 4.0).astype(np.float32)
    items = rng.integers(0, 500, size=n2).astype(np.int32)
    return (jnp.asarray(x), jnp.asarray(y), jnp.asarray(items),
            jnp.asarray(valid), jnp.asarray(gids))


def _tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- ref oracle vs interpret-mode Pallas body ---------------------------------

@pytest.mark.parametrize("sentinel", [False, True])
def test_linregr_kernel_matches_ref(sentinel):
    x, y, _, valid, gids = _layout(sentinel=sentinel)
    want = sf_ref.segment_linregr_ref(x, y, valid, gids, num_groups=G)
    got = sf_ops.segment_linregr(x, y, valid, gids, num_groups=G)
    _tree_equal(got, want)


@pytest.mark.parametrize("sentinel", [False, True])
def test_countmin_kernel_matches_ref(sentinel):
    _, _, items, valid, gids = _layout(sentinel=sentinel, seed=1)
    want = sf_ref.segment_countmin_ref(items, valid, gids, depth=4,
                                       width=128, num_groups=G)
    got = sf_ops.segment_countmin(items, valid, gids, depth=4, width=128,
                                  num_groups=G)
    _tree_equal(got, want)


@pytest.mark.parametrize("sentinel", [False, True])
@pytest.mark.parametrize("bits", [16, 32])
def test_fm_kernel_matches_ref(sentinel, bits):
    """Covers both FM bit widths — including the bits-1 fallback when a
    hash has no set bit inside the window (the argmax-free lowbit
    formulation in the kernel must reproduce the oracle exactly)."""
    _, _, items, valid, gids = _layout(sentinel=sentinel, seed=2)
    want = sf_ref.segment_fm_ref(items, valid, gids, num_hashes=4,
                                 bits=bits, num_groups=G)
    got = sf_ops.segment_fm(items, valid, gids, num_hashes=4, bits=bits,
                            num_groups=G)
    _tree_equal(got, want)


def test_torn_layout_fails_loudly():
    x, y, _, valid, gids = _layout()
    with pytest.raises(ValueError, match="equal group-aligned blocks"):
        sf_ops.segment_linregr(x[:-1], y[:-1], valid[:-1], gids,
                               num_groups=G)
    with pytest.raises(ValueError, match="equal blocks"):
        sf_ref.segment_linregr_ref(x[:-1], y[:-1], valid[:-1], gids,
                                   num_groups=G)


# -- registry resolve semantics -----------------------------------------------

def test_auto_resolves_ref_off_tpu():
    x, y, _, valid, gids = _layout()
    entry = registry.get("segment_linregr")
    if jax.default_backend() != "tpu":
        assert entry.resolve("auto", x, y, valid, gids,
                             num_groups=G) == ("ref", {})


def test_forced_pallas_off_tpu_warns_once_and_runs_interpret():
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU interpret-mode semantics")
    x, y, _, valid, gids = _layout()
    entry = registry.get("segment_linregr")
    registry._WARNED_INTERPRET.discard("segment_linregr")
    with pytest.warns(UserWarning, match="interpret mode"):
        assert entry.resolve("pallas", x, y, valid, gids,
                             num_groups=G)[0] == "pallas"
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second resolve: silent
        assert entry.resolve("pallas", x, y, valid, gids,
                             num_groups=G)[0] == "pallas"


def test_forced_pallas_on_tpu_unsupported_shape_raises(monkeypatch):
    """Satellite contract: on a TPU backend, forcing impl='pallas' for a
    call the supports gate rejects must fail loudly — never silently
    degrade to ref."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, _, items, valid, gids = _layout()
    entry = registry.get("segment_fm")
    # bits=16 fails the compiled kernel's lane gate (bits % 128)
    with pytest.raises(ValueError, match="supports gate rejected"):
        entry.resolve("pallas", items, valid, gids, num_hashes=4, bits=16,
                      num_groups=G)
    # auto with the same shapes degrades to ref instead
    assert entry.resolve("auto", items, valid, gids, num_hashes=4,
                         bits=16, num_groups=G) == ("ref", {})


def test_supports_runs_on_shape_structs():
    """Host-side resolution probes supports with ShapeDtypeStructs; the
    compiled kernels stream whole 128-lane blocks only."""
    x = jax.ShapeDtypeStruct((768, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((768,), jnp.float32)
    valid = jax.ShapeDtypeStruct((768,), jnp.bool_)
    gids = jax.ShapeDtypeStruct((6,), jnp.int32)
    assert sf_ops.segment_linregr_supports(x, y, valid, gids,
                                           num_groups=G) is True
    bad = jax.ShapeDtypeStruct((768, 3), jnp.float64)
    assert sf_ops.segment_linregr_supports(bad, y, valid, gids,
                                           num_groups=G) is False
    ragged = jax.ShapeDtypeStruct((96,), jnp.float32)   # 16-row blocks
    assert sf_ops.segment_linregr_supports(
        jax.ShapeDtypeStruct((96, 3), jnp.float32), ragged, ragged, gids,
        num_groups=G) is False


def test_supports_ranker_tuned_kwargs(monkeypatch):
    """supports may return tuned kwargs (a ranker, not just a gate):
    they flow into the pallas impl only, and caller kwargs win."""
    calls = {}

    def fake_pallas(x, *, tile_n=1):
        calls["tile_n"] = tile_n
        return x

    registry.register("_test_ranker", ref=lambda x, **kw: x,
                      pallas=fake_pallas,
                      supports=lambda x, **kw: {"tile_n": 77},
                      overwrite=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.zeros((8,))
    registry.dispatch("_test_ranker", x)
    assert calls["tile_n"] == 77                       # tuned kwarg applied
    registry.dispatch("_test_ranker", x, tile_n=5)
    assert calls["tile_n"] == 5                        # caller wins


def test_calibration_feeds_kernel_rankers(monkeypatch):
    """The built-in xtx/countmin rankers read tuned tile sizes from the
    ACTIVE calibration (no calibration -> plain True)."""
    from repro.core.calibration import Calibration, use
    entry = registry.get("xtx")
    x = jnp.zeros((64, 3), jnp.float32)
    y = jnp.zeros((64,), jnp.float32)
    assert entry.supports(x, y) is True
    cal = Calibration(backend="tpu", timestamp="t", engines={},
                      kernels={"xtx": {"tile_n": 256}}, grouped_block=[])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with use(cal):
        assert entry.supports(x, y) == {"tile_n": 256}
        assert entry.resolve("auto", x, y) == ("pallas", {"tile_n": 256})


# -- trace recording + engine integration -------------------------------------

def _table(n=160, seed=3):
    rng = np.random.default_rng(seed)
    return Table.from_columns({
        "x": jnp.asarray((rng.integers(-8, 8, (n, 3)) / 4).astype(np.float32)),
        "y": jnp.asarray((rng.integers(-8, 8, (n,)) / 4).astype(np.float32)),
        "item": jnp.asarray(rng.integers(0, 99, n).astype(np.int32)),
        "g": jnp.asarray((np.arange(n) % G).astype(np.int32)),
    })


def test_grouped_execution_records_resolved_kernel():
    tbl = _table()
    with trace_execution() as t:
        run_grouped(LinregrAggregate(use_kernel=True), tbl, "g", G)
    assert len(t.kernels) == 1
    ev = t.kernels[0]
    assert ev.detail["name"] == "segment_linregr"
    assert ev.detail["requested"] == "auto"
    expect = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert ev.engine == expect
    # no kernel requested -> no kernel event
    with trace_execution() as t:
        run_grouped(LinregrAggregate(), tbl, "g", G)
    assert t.kernels == []


def test_forced_ref_records_and_runs():
    tbl = _table()
    with trace_execution() as t:
        run_grouped(CountMinAggregate(4, 128, use_kernel="ref"),
                    tbl, "g", G)
    assert [(e.engine, e.detail["requested"]) for e in t.kernels] \
        == [("ref", "ref")]


def test_single_member_fused_forwards_kernel_hook():
    one = FusedAggregate([CountMinAggregate(4, 128, use_kernel="ref")])
    assert one.segment_kernel == "segment_countmin"
    assert one.kernel_impl == "ref"
    assert one.cost_class == "sketch"
    many = FusedAggregate([CountMinAggregate(4, 128, use_kernel="ref"),
                           FMAggregate(4, 16)])
    assert many.segment_kernel is None
    assert many.kernel_impl is None
    assert many.cost_class == "generic"


def test_planned_single_grouped_statement_uses_kernel():
    """Through the FULL plan layer (GroupedScanAgg -> single-member
    fusion -> run_grouped) the kernel hook must survive projection and
    fusion wrappers, and the result must stay bit-identical."""
    from repro.core import GroupedScanAgg, execute
    tbl = _table()
    base = execute(GroupedScanAgg(CountMinAggregate(4, 128), tbl, "g", G,
                                  columns=("item",)))
    with trace_execution() as t:
        got = execute(GroupedScanAgg(
            CountMinAggregate(4, 128, use_kernel="ref"), tbl, "g", G,
            columns=("item",)))
    assert [e.detail["name"] for e in t.kernels] == ["segment_countmin"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
