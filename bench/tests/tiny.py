"""Small cells for the CPU tests: the real configurations' engine
constants and limits on a small fabric and a short horizon.  A
configuration's ``cpu_test`` section names its own ``fabric`` and
``job`` at a CPU test's size; without one, an 8-host ring stands in."""
from __future__ import annotations

import copy
import time

from lib.cells import BENCH, ROOT, Cell, load_cell, load_json

FABRIC = dict(kind="leaf_spine", n_hosts=8, n_tors=2, n_spines=2,
              host_gbps=10, fabric_gbps=10)
JOB = dict(kind="ring_allreduce", ring=4, chunk_bytes=200000.0, passes=1)


def cell(name: str, lanes_axes=None, backend: str = "xla",
         chips: int | None = None, bench_file=ROOT / "BENCHMARK.json",
         base=BENCH) -> Cell:
    """The cell ``name`` of ``bench_file`` cut to a CPU test's size: the
    configuration's ``cpu_test`` fabric and job, 100-tick dispatches or
    40-tick windows, the XLA engine (the kernel runs interpreted on a
    CPU)."""
    c = copy.deepcopy(load_cell(name, bench_file, base))
    small = c.config.get("cpu_test", {"fabric": FABRIC, "job": JOB})
    c.config.update(fabric=dict(small["fabric"]), job=dict(small["job"]),
                    horizon_ticks=100)
    tr = c.traffic
    tr["path"] = {"backend": backend}
    tr["trace_units"] = 1
    if tr["kind"] == "sweep":
        tr["axes"] = lanes_axes or {"sym_on": [0, 1], "k": [0.01, 0.1]}
    else:
        tr.update(window_ticks=40, check_steps=3, check_from_steps=6)
    if chips is not None:
        c.chips = chips
    return c


def now() -> float:
    return time.perf_counter()


__all__ = ["cell", "now", "BENCH", "load_json"]
