"""Host time of the planner per completed statement (ms): the program's
``madjax.plan`` spans (fusion, the segment-ops probe, the grouped method's
ranking), clipped to the window.  Nothing to read where the program opens
no such span."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_statement(ctx, "madjax.plan")
