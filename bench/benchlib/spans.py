"""Host time inside the program's own spans, read from a reduced trace.

The program opens ``jax.profiler.TraceAnnotation("madjax.<kind>")`` around
its host work on the statement path (``repro.core.trace.span``), so each
span lands in the reduced trace's ``host`` list (``devtrace``) beside the
benchmark's window span and on the device's clock.  A per-layer metric
reads the milliseconds spent inside one span name, per completed
statement.  A program that opens no such span gives nothing to read.
"""

from __future__ import annotations


def host_ns(trace: dict, window, name: str) -> tuple[float, int]:
    """Nanoseconds inside the host spans called ``name``, each clipped to
    ``window``, and how many of them fall inside it."""
    w0, w1 = window
    total, count = 0.0, 0
    for n, s, d in trace["host"]:
        if n != name:
            continue
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            total += b - a
            count += 1
    return total, count


def ms_per_statement(ctx, name: str) -> float | None:
    """Milliseconds inside spans called ``name``, clipped to the window,
    over the window's completed statements; ``None`` where no such span
    falls in the window or no statement completed."""
    ns, count = host_ns(ctx.trace, ctx.window_ns, name)
    if not count or not ctx.done:
        return None
    return ns / 1e6 / len(ctx.done)
