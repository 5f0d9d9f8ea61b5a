"""The program's inputs for a configuration file and a knob point.

The fabric and the job are built by the ``program(...)`` functions of the
kind files the configuration names (``bench/fabrics/<kind>.py``,
``bench/jobs/<kind>.py``; see ``cells.py``), and ``SimParams`` from the
configuration's engine constants.  Both are copied in substance from the
scenario registry of ``benchmarks/common.py`` (``table1_ring``,
``fat_tree_multipod``, ``knob_grid``); the benchmark keeps its own copy so
that an edit of the registry cannot move the yardstick.
"""
from __future__ import annotations

import itertools

from repro.core.netsim import SimParams
from repro.core.symphony import SymphonyParams

from .cells import BENCH, kind

SYM_FIELDS = ("k", "tau", "n_warmup", "n_sample", "alpha_max")
STRUCT_FIELDS = ("backend", "segsum", "blk", "tick_window")


def program_inputs(config: dict, base=BENCH):
    """``(topology, workload)`` of a configuration, built by the program
    through the kind files under ``base``."""
    fabric, args = kind("fabrics", config["fabric"], base)
    topo = fabric.program(**args)
    job, args = kind("jobs", config["job"], base)
    return topo, job.program(topo.n_hosts, **args)


def knob_points(config: dict, traffic: dict) -> list[dict]:
    """Every lane's engine values: the configuration's engine section, the
    mix's fixed ``knobs``, then the row-major cross product of its
    ``axes`` (first axis slowest, as ``knob_grid`` orders a sweep)."""
    base = {**config["engine"], **traffic.get("knobs", {})}
    axes = traffic.get("axes", {})
    points = []
    for combo in itertools.product(*axes.values()):
        points.append({**base, **dict(zip(axes, combo))})
    return points or [base]


def sim_params(point: dict, traffic: dict, n_ticks: int) -> SimParams:
    """``SimParams`` of one lane on the mix's engine path."""
    fields = set(SimParams._fields)
    kw = {k: v for k, v in point.items() if k in fields}
    kw["sym"] = SymphonyParams(*(point[f] for f in SYM_FIELDS))
    kw["sym_on"] = bool(point["sym_on"])
    kw.update({k: traffic["path"][k] for k in STRUCT_FIELDS
               if k in traffic["path"]})
    return SimParams(n_ticks=n_ticks, **kw)
