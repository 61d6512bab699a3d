"""Device time of the grouped layout per completed statement (ms).

The grouped layout's programs are the partitioning sort (``jit_argsort``,
``jit_sort``), the gather that builds the partitioned copy
(``jit_take_rows``) and the block-window copy that builds the
group-aligned one (``jit_take_rows_blocked``), all matched by prefix; in
the window the sort and the partitioned copy are memoized, so the window
copy is what it reads.  Nothing to read where none ran in the window."""

from benchlib import devtrace

LAYOUT_PROGRAMS = ("jit_take_rows", "jit_argsort", "jit_sort")


def read(ctx):
    ns = devtrace.program_ns(ctx.trace, ctx.window_ns, LAYOUT_PROGRAMS)
    if ns <= 0 or not ctx.done:
        return None
    return ns / 1e6 / len(ctx.done)
