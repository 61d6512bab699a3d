"""The fold's share of its roofline (%).

The least time is what the window's completed statements need by their
shapes alone on the cell's chips (``benchlib.roofline``: operations over
the chips' peak FLOP/s or bytes over their peak HBM bandwidth, whichever
is larger), over the device time per chip of the programs that fold them:
the ungrouped pass (``jit_go``), the grouped segment fold on one chip
(``jit_go_segment``) and across chips (``jit_shard_segment``, the merge of
the chips' partial states included), finalize included.  Nothing to read
where no such program ran."""

from benchlib import devtrace, roofline

FOLD_PROGRAMS = ("jit_go", "jit_go_segment", "jit_shard_segment")


def read(ctx):
    fold_s = devtrace.program_ns(ctx.trace, ctx.window_ns, FOLD_PROGRAMS,
                                 exact=True) / 1e9
    if fold_s <= 0 or not ctx.done:
        return None
    least = sum(roofline.least_seconds(r.kind, r.rows, r.k, ctx.device_kind,
                                       ctx.cell.chips)
                for r in ctx.done)
    return 100.0 * least / fold_s
