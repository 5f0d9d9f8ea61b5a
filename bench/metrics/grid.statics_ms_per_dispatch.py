"""Host milliseconds per ``simulate_grid`` dispatch spent building the
per-seed static arrays: the time inside the program's
``netsim.grid.statics`` spans in which no chip was busy, per
``netsim.grid`` span.  Silent where the program opens no such spans."""
from lib import program_trace as pt


def read(ctx):
    s = pt.of(ctx).per_span(("netsim.grid.statics",), "netsim.grid")
    return None if s is None else 1e3 * s
