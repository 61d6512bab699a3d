"""The analytics kernels compile for a TPU v5e, at the widths the main path
uses, without a chip attached.

Interpret-mode tests pin what the kernels compute; only the chip's own
compiler sees what Mosaic refuses: block tiling, VMEM footprint, layouts
it cannot lower.  Each case compiles one ``*_padded`` kernel with
``interpret=False`` for one core of a described ``v5e:2x2`` and checks a
Mosaic kernel (``tpu_custom_call``) is in the program.  The shapes are
what the wrappers hand the kernels for a 10M-row table with 80
independent variables and 64 groups.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import registry
from repro.kernels.countmin import ops as cm_ops
from repro.kernels.countmin.kernel import countmin_padded
from repro.kernels.kmeans_assign.kernel import assign_reduce_padded
from repro.kernels.segment_fold import kernel as sf_kernel, ops as sf_ops
from repro.kernels.xtx.kernel import xtx_xty_padded

ROWS = 10_000_000
K = 80
GROUPS = 64
WIDTH = 1024                       # count-min width of the main path
BLOCK = 4096                       # segment block the layout uses at ROWS
NB = ROWS // BLOCK + GROUPS        # blocks incl. each group's partial one
N2 = NB * BLOCK
F32, I32 = jnp.float32, jnp.int32


def _padded(tile: int) -> int:
    """ROWS padded to whole tiles, as the ungrouped wrappers pad."""
    return -(-ROWS // tile) * tile


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip executables cannot be read back from the persistent
    # cache without a chip, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_xtx_compiles(one_chip):
    n = _padded(1024)
    _compile(one_chip,
             lambda xt, y: xtx_xty_padded(xt, y, tile_n=1024,
                                          interpret=False),
             ((K, n), F32), ((1, n), F32))


def test_kmeans_assign_compiles(one_chip):
    # K = 80 features pad to 128 lanes, 8 centroids to 8 sublanes
    n = 8192 * 512
    _compile(one_chip,
             lambda x, c, m: assign_reduce_padded(x, c, m, tile_n=512,
                                                  interpret=False),
             ((n, 128), F32), ((8, 128), F32), ((n, 1), F32))


@pytest.mark.parametrize("width", [WIDTH, 2048, 4096])
def test_countmin_compiles_at_the_tile_supports_picks(one_chip, width):
    tuned = registry.get("countmin").supports(
        jax.ShapeDtypeStruct((ROWS,), I32), jax.ShapeDtypeStruct((ROWS,), I32),
        4, width)
    tile = tuned["tile_n"]
    assert tile <= cm_ops.max_tile(width) and tile % 128 == 0
    n = _padded(tile)
    _compile(one_chip,
             lambda i, m: countmin_padded(i, m, depth=4, width=width,
                                          tile_n=tile, interpret=False),
             ((1, n), I32), ((1, n), I32))


def test_countmin_supports_rejects_what_the_compiler_refuses(one_chip):
    """At width 32768 even a one-tile (width, 128) one-hot outgrows
    VMEM: the compiler refuses the kernel, and the gate never admits
    it."""
    width, tile = 32768, 128
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _compile(one_chip,
                 lambda i, m: countmin_padded(i, m, depth=4, width=width,
                                              tile_n=tile, interpret=False),
                 ((1, 8 * tile), I32), ((1, 8 * tile), I32))
    spec = jax.ShapeDtypeStruct((ROWS,), I32)
    supports = registry.get("countmin").supports
    assert supports(spec, spec, 4, width) is False
    assert supports(spec, spec, 4, width, tile_n=tile) is False
    # the compiler once refused width 2048 at the old default tile 2048
    # for VMEM: the gate takes that tile at that width no more
    assert supports(spec, spec, 4, 2048, tile_n=2048) is False


def test_segment_linregr_compiles(one_chip):
    x = jax.ShapeDtypeStruct((N2, K), F32)
    v = jax.ShapeDtypeStruct((N2,), F32)
    gids = jax.ShapeDtypeStruct((NB,), I32)
    assert sf_ops.segment_linregr_supports(x, v, v, gids,
                                           num_groups=GROUPS)
    _compile(one_chip,
             lambda xt, y, m, g: sf_kernel.segment_linregr_padded(
                 xt, y, m, g, num_groups=GROUPS, block_size=BLOCK,
                 interpret=False),
             ((K, N2), F32), ((1, N2), F32), ((1, N2), F32), ((NB,), I32))


def test_segment_countmin_compiles(one_chip):
    items = jax.ShapeDtypeStruct((N2,), I32)
    gids = jax.ShapeDtypeStruct((NB,), I32)
    assert sf_ops.segment_countmin_supports(items, items, gids, depth=4,
                                            width=WIDTH, num_groups=GROUPS)
    _compile(one_chip,
             lambda i, m, g: sf_kernel.segment_countmin_padded(
                 i, m, g, depth=4, width=WIDTH, num_groups=GROUPS,
                 block_size=BLOCK, interpret=False),
             ((1, N2), I32), ((1, N2), I32), ((NB,), I32))


def test_segment_fm_compiles(one_chip):
    items = jax.ShapeDtypeStruct((N2,), I32)
    gids = jax.ShapeDtypeStruct((NB,), I32)
    assert sf_ops.segment_fm_supports(items, items, gids, num_hashes=8,
                                      bits=128, num_groups=GROUPS)
    _compile(one_chip,
             lambda i, m, g: sf_kernel.segment_fm_padded(
                 i, m, g, num_hashes=8, bits=128, num_groups=GROUPS,
                 block_size=BLOCK, interpret=False),
             ((1, N2), I32), ((1, N2), I32), ((NB,), I32))
