"""The fold's share of its roofline (%).

The least time is what the window's completed statements need by their
shapes alone (``benchlib.roofline``: operations over peak FLOP/s or bytes
over peak HBM bandwidth, whichever is larger), over the device time of the
programs that fold them: the ungrouped pass (``jit_go``) and the grouped
segment fold (``jit_go_segment``), finalize included.  Nothing to read
where no such program ran."""

from benchlib import devtrace, roofline

FOLD_PROGRAMS = ("jit_go", "jit_go_segment")


def read(ctx):
    fold_s = devtrace.program_ns(ctx.trace, ctx.window_ns, FOLD_PROGRAMS,
                                 exact=True) / 1e9
    if fold_s <= 0 or not ctx.done:
        return None
    least = sum(roofline.least_seconds(r.kind, r.rows, r.k, ctx.device_kind)
                for r in ctx.done)
    return 100.0 * least / fold_s
