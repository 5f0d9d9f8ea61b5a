"""Host milliseconds per ``SimController.step()`` spent taking the window
off the chip: the time inside the program's ``netsim.window.unbatch`` and
``netsim.step.observe`` spans (the readbacks and the window summary) in
which no chip was busy, per ``netsim.step`` span.  Silent where the
program opens no such spans."""
from lib import program_trace as pt

SPANS = ("netsim.window.unbatch", "netsim.step.observe")


def read(ctx):
    s = pt.of(ctx).per_span(SPANS, "netsim.step")
    return None if s is None else 1e3 * s
