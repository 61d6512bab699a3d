"""Host time of the grouped layout's index build per completed statement
(ms): the program's ``madjax.layout.index`` spans (the counts and offsets
synced to the host, the aligned index built in numpy, its upload),
clipped to the window; the device is idle through them.  Nothing to read
where the program opens no such span."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_statement(ctx, "madjax.layout.index")
