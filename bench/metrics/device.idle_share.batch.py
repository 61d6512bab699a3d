"""Share of the traced window in which no program ran on the device (%).

Busy time is the union of the device's program executions (the trace's
``XLA Modules`` line) inside the benchmark's window span."""

from benchlib import devtrace


def read(ctx):
    return devtrace.idle_share_pct(ctx.trace, ctx.window_ns)
