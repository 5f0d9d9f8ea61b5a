"""The engine tick's share of its HBM roofline: the least time of one
lane-tick (its carried state read and written once at the chip's peak
HBM bandwidth, ``lib/roofline.py``) over the measured device time per
lane-tick (every operation, summed over chips)."""


def read(ctx):
    device_s = sum(ctx.red.ops.values())
    if device_s <= 0 or not ctx.lane_ticks:
        return None
    least = ctx.tick_bytes() / ctx.hbm_peak()
    return 100.0 * least / (device_s / ctx.lane_ticks)
