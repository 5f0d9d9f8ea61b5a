"""Device microseconds per lane-tick of every operation that is not the
fused ``netsim_tick`` kernel (the engine's XLA stages and the ops around
the kernel), summed over chips.  The kernel is the Mosaic custom call,
as in ``kernel.netsim_tick_us_per_lane_tick``."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    if not ctx.lane_ticks:
        return None
    other = sum(ctx.red.ops.values()) - ctx.red.matching(KERNEL)
    return 1e6 * other / ctx.lane_ticks
