"""Device microseconds per lane-tick of the operations in the engine's
``netsim.marking`` scope (``stages.stage_marking``), summed over
chips.  Silent where no operation of the window carries a ``netsim.``
scope."""
from lib import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if not prog.scoped or not ctx.lane_ticks:
        return None
    return 1e6 * prog.scope_s("netsim.marking") / ctx.lane_ticks
