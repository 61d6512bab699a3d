"""Device time of the grouped layout per completed statement (ms).

The grouped layout's programs are the partitioning sort and the
column-at-a-time row gathers (``jit_take_rows``) that build the partitioned
and the group-aligned copies of the table.  Nothing to read where none ran
in the window."""

from benchlib import devtrace

LAYOUT_PROGRAMS = ("jit_take_rows", "jit_argsort", "jit_sort")


def read(ctx):
    ns = devtrace.program_ns(ctx.trace, ctx.window_ns, LAYOUT_PROGRAMS)
    if ns <= 0 or not ctx.done:
        return None
    return ns / 1e6 / len(ctx.done)
