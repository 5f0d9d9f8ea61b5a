"""Host milliseconds per ``SimController.step()``: the time inside the
benchmark's ``bench.step`` spans in which no chip was busy, per step."""


def read(ctx):
    n = ctx.red.span_count.get("bench.step", 0)
    if not n:
        return None
    return 1e3 * ctx.red.host_s["bench.step"] / n
