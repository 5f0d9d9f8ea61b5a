"""Host milliseconds per ``SimController.step()`` spent putting the next
window on the chip: the time inside the program's ``netsim.step.action``,
``netsim.window.batch`` and ``netsim.window.launch`` spans in which no
chip was busy, per ``netsim.step`` span.  Silent where the program opens
no such spans."""
from lib import program_trace as pt

SPANS = ("netsim.step.action", "netsim.window.batch", "netsim.window.launch")


def read(ctx):
    s = pt.of(ctx).per_span(SPANS, "netsim.step")
    return None if s is None else 1e3 * s
