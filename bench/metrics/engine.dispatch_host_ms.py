"""Host time of the engine's fold dispatch per completed statement (ms):
the program's ``madjax.fold.dispatch`` spans (kernel resolution, the
prepared-program lookup, and on a miss the tracing and cache load of the
fold program), clipped to the window.  Nothing to read where the program
opens no such span."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_statement(ctx, "madjax.fold.dispatch")
