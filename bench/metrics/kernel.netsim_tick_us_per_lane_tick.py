"""Device microseconds of the fused ``netsim_tick`` kernel per lane-tick:
the kernel's custom-call operations, summed over chips, over the
lane-ticks of the traced window.  Silent where the kernel did not run.

The ``pallas_call`` carries no name of its own in the trace yet; its
operation is the Mosaic custom call, the only one on these paths."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    s = ctx.red.matching(KERNEL)
    if s <= 0 or not ctx.lane_ticks:
        return None
    return 1e6 * s / ctx.lane_ticks
