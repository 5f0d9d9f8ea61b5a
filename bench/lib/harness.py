"""One run of one cell: set up, warm up, measure, check, report.

The run builds the cell's deployment, warms up every program the window
will run with one dispatch or step of the cell's own traffic (set-up
ends there), then drives the traffic for ``--seconds`` and starts
nothing after that deadline.  A trace or compile inside the window fails
the run.  After the window it reads the chips' peak memory, frees the
program's state, and sets what the window produced beside the plain
reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces ``trace_units`` dispatches or steps of the mix in a window of
their own and reports the per-layer metrics, read from the trace by the
files in ``bench/metrics``.  The trace stays in ``.bench/trace`` until
the next traced run.  The last line on standard output is the
result; the last lines on standard error are the compared numbers and
their limits.
"""
from __future__ import annotations

import json
import shutil
import sys
import time

import jax

from . import compare, trace as tr
from .cells import ROOT, metric_reader
from .chip import CompileClock, peak_bytes
from .kinds import KINDS
from .roofline import peak, tick_bytes

TRACE_DIR = ROOT / ".bench" / "trace"


def _info(out, **fields):
    print(json.dumps({"info": fields.pop("what"), **fields}), file=out,
          flush=True)


def run(cell, seed: int, seconds: float, trace: bool, devices, t_start: float,
        clock: CompileClock, out=sys.stdout, err=sys.stderr,
        kind_cls=None) -> dict:
    from repro.core.netsim import core_trace_count

    t_init = time.perf_counter()
    mix = (kind_cls or KINDS[cell.traffic["kind"]])(cell, seed)
    t_build = time.perf_counter()
    mix.warm_up()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    _info(out, what="setup", setup_s=setup_s, runtime_init_s=t_init - t_start,
          build_s=t_build - t_init, warmup_s=t_warm - t_build,
          compile_s=clock.seconds)

    events, traces = clock.events, core_trace_count()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(TRACE_DIR), profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                mix.window(seconds, cell.traffic["trace_units"])
    else:
        mix.window(seconds)
    if clock.events != events or core_trace_count() != traces:
        raise RuntimeError(
            f"the window traced or compiled: {clock.events - events} "
            f"compile events, {core_trace_count() - traces} engine traces")
    measured = mix.measured()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}

    result = {"correct": False, "attempted": measured["units"], "failed": 0}
    if trace:
        red = tr.load(TRACE_DIR, len(devices))
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        ctx = MetricContext(cell, measured, red, devices[0].device_kind)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(red)
    else:
        metrics = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in measured}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        _info(out, what="window", **measured)

    t_ref = time.perf_counter()
    mix.take_outputs()
    readings = mix.readings()
    correct, check = compare.judge(readings, cell.limits)
    _info(out, what="reference", reference_s=time.perf_counter() - t_ref)
    result.update(correct=correct, metrics=metrics, device=device,
                  check=check)
    for name, c in check.items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result


class MetricContext:
    """What a per-layer metric's reader may read: the reduced trace, the
    traced window's counts, the cell's shapes and the chip's peaks."""

    def __init__(self, cell, measured, red, device_kind):
        self.cell, self.red, self.device_kind = cell, red, device_kind
        self.lane_ticks = measured["lane_ticks"]

    def shapes(self) -> dict:
        from reference.build import deployment

        dep = deployment(self.cell.config)
        return dict(F=dep.F, W=dep.window, L=dep.L, J=dep.J, D=dep.D)

    def tick_bytes(self) -> int:
        return tick_bytes(**self.shapes())

    def hbm_peak(self) -> float:
        return peak(self.device_kind)
