"""Host milliseconds per ``simulate_grid`` dispatch: the time inside the
benchmark's ``bench.dispatch`` spans in which no chip was busy, per
dispatch."""


def read(ctx):
    n = ctx.red.span_count.get("bench.dispatch", 0)
    if not n:
        return None
    return 1e3 * ctx.red.host_s["bench.dispatch"] / n
