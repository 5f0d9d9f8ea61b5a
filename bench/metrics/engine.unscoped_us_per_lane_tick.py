"""Device microseconds per lane-tick of the operations that are neither
the fused kernel nor in any ``netsim.`` scope (the scan and record
bookkeeping around the stages), summed over chips.  Silent where no
operation of the window carries a ``netsim.`` scope."""
from lib import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if not prog.scoped or not ctx.lane_ticks:
        return None
    return 1e6 * prog.unscoped_s() / ctx.lane_ticks
