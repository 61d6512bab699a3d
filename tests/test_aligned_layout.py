"""The group-aligned layout (:meth:`GroupedView.aligned_blocks`) built by
block-window copies: bit-identical on valid rows to the per-row index it
replaced, zeros on padding rows, an index on the host that scales with the
blocks and not the rows, and a ``layout.gather`` span that counts the
copied blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Table, trace_execution

# group sizes by layout; -1 / G ids fall outside every segment
LAYOUTS = {
    # ragged, group 1 empty, the last group ends at the table's end
    "ragged": ([5, 0, 37, 1, 70, 12], 0, 0),
    # out-of-range ids before and after: the last group ends a few rows
    # short of the table's end, inside its last block
    "tail_ids": ([9, 40, 0, 23], 3, 6),
    # fewer rows than the larger block sizes
    "small_n": ([20, 0, 11, 19], 0, 0),
}


def _table(layout: str, width: int | None, seed: int = 0):
    sizes, below, above = LAYOUTS[layout]
    G = len(sizes)
    rng = np.random.default_rng(seed)
    g = np.concatenate([np.full(below, -1), np.repeat(np.arange(G), sizes),
                        np.full(above, G)]).astype(np.int32)
    g = rng.permutation(g)
    n = len(g)
    shape = (n,) if width is None else (n, width)
    t = Table.from_columns({
        "v": jnp.asarray(rng.normal(size=shape), jnp.float32),
        "g": jnp.asarray(g)})
    return t, G, jnp.asarray(rng.random(n) < 0.6)


def _old_index(view, bs: int, pad_blocks_to: int | None):
    """The per-row ``src`` / ``valid`` index the layout was once built
    from, and its block gids."""
    G = view.num_groups
    counts = np.asarray(view.counts)
    starts = np.asarray(view.offsets)[:-1]
    bpg = -(-counts // bs)
    bg = np.repeat(np.arange(G), bpg).astype(np.int32)
    ppg = bpg * bs
    grp = np.repeat(np.arange(G), ppg)
    out_start = np.concatenate([[0], np.cumsum(ppg)])[:-1]
    local = np.arange(int(ppg.sum())) - out_start[grp]
    valid = local < counts[grp]
    src = np.where(valid, starts[grp] + local, 0)
    if pad_blocks_to:
        extra = -len(bg) % pad_blocks_to
        bg = np.concatenate([bg, np.full(extra, G, np.int32)])
        src = np.concatenate([src, np.zeros(extra * bs, src.dtype)])
        valid = np.concatenate([valid, np.zeros(extra * bs, bool)])
    return src, valid, bg


@pytest.mark.parametrize("extra", ["plain", "pad", "mask"])
@pytest.mark.parametrize("width", [None, 3])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("bs", [4, 17, 64])
def test_window_layout_matches_the_row_index(bs, layout, width, extra):
    t, G, mask = _table(layout, width)
    view = t.group_by("g", G)
    pad = 3 if extra == "pad" else None
    pmask = view.permute(mask) if extra == "mask" else None
    cols, valid, bgids = view.aligned_blocks(bs, pmask, pad_blocks_to=pad)

    src, in_seg, bg = _old_index(view, bs, pad)
    want_valid = in_seg & (True if pmask is None else np.asarray(pmask)[src])
    np.testing.assert_array_equal(np.asarray(bgids), bg)
    np.testing.assert_array_equal(np.asarray(valid), want_valid)
    got = np.asarray(cols["v"])
    part = np.asarray(view.table["v"])
    assert got.shape == (len(bg) * bs,) + part.shape[1:]
    # bit-identical on the segments' rows, zeros on every padding row
    np.testing.assert_array_equal(got[in_seg], part[src[in_seg]])
    assert not np.any(got[~in_seg])


def test_gather_span_counts_the_copied_blocks():
    t, G, _ = _table("ragged", 3)
    view = t.group_by("g", G)
    with trace_execution() as tr:
        _, _, bgids = view.aligned_blocks(17, pad_blocks_to=4)
    (ev,) = tr.spans("layout.gather")
    sentinels = int(np.sum(np.asarray(bgids) == G))
    assert sentinels > 0
    assert ev.detail["blocks"] == bgids.shape[0] - sentinels
    assert ev.detail["row_gathered"] == 0


def test_index_is_built_over_blocks_not_rows():
    """At 1M rows and 25 groups the host's index took 0.7-0.8 ms on a
    CPU (best of five), and the per-row index it replaced 20-40 ms; the
    limit allows about five times the former."""
    n, G = 1_000_000, 25
    rng = np.random.default_rng(1)
    t = Table.from_columns({
        "y": jnp.asarray(rng.normal(size=n), jnp.float32),
        "g": jnp.asarray(rng.integers(0, G, n), jnp.int32)})
    view = t.group_by("g", G)
    jax.block_until_ready((view.counts, view.offsets, view.table["y"]))
    best = np.inf
    for _ in range(5):
        with trace_execution() as tr:
            out = view.aligned_blocks(4096)
        jax.block_until_ready(out)
        (ev,) = tr.spans("layout.index")
        best = min(best, (ev.t1_ns - ev.t0_ns) / 1e6)
    assert best < 4.0
