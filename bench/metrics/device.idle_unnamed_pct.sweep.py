"""Share of the traced window in which no chip ran an operation and no
``netsim.*`` host span of the program was open, in a sweep cell: idle time
that no part of the program accounts for.  Silent where the program
opens no such spans."""
from lib import program_trace as pt


def read(ctx):
    prog = pt.of(ctx)
    if not prog.count or prog.window_s <= 0:
        return None
    return 100.0 * prog.idle_unnamed_s / prog.window_s
