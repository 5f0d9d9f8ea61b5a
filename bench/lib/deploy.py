"""The program's inputs for a configuration file and a knob point.

Copied in substance from the scenario registry of ``benchmarks/common.py``
(``table1_ring``, ``fat_tree_multipod``, ``knob_grid``): a fabric built
with the simulator's public constructors, one interleaved ring all-reduce
job, and ``SimParams`` with the configuration's engine constants.  The
benchmark keeps its own copy so that an edit of the registry cannot move
the yardstick.  Link rates are stated in Gbit/s in the configuration;
the simulator's constructors take them as oversubscription factors.
"""
from __future__ import annotations

import itertools

from repro.core.netsim import (SimParams, WorkloadBuilder, make_fat_tree,
                               make_leaf_spine)
from repro.core.symphony import SymphonyParams

GBPS = 1e9 / 8.0
SYM_FIELDS = ("k", "tau", "n_warmup", "n_sample", "alpha_max")
STRUCT_FIELDS = ("backend", "segsum", "blk", "tick_window")


def _leaf_spine(n_hosts, n_tors, n_spines, host_gbps, fabric_gbps):
    return make_leaf_spine(
        n_hosts, n_tors, n_spines, host_gbps * GBPS,
        oversubscription=host_gbps * (n_hosts / n_tors) / n_spines
        / fabric_gbps)


def _fat_tree(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
              host_gbps, edge_gbps, core_gbps):
    return make_fat_tree(
        n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
        link_bps=host_gbps * GBPS,
        oversubscription=host_gbps * hosts_per_tor / spines_per_pod
        / edge_gbps,
        core_oversubscription=host_gbps * tors_per_pod * hosts_per_tor
        / n_cores / core_gbps)


def _ring_allreduce(n_hosts, ring, chunk_bytes, passes):
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk_bytes, passes=passes, barrier=False)
    return b.build()


FABRICS = {"leaf_spine": _leaf_spine, "fat_tree": _fat_tree}
JOBS = {"ring_allreduce": _ring_allreduce}


def program_inputs(config: dict):
    """``(topology, workload)`` of a configuration, built by the program."""
    fab = dict(config["fabric"])
    topo = FABRICS[fab.pop("kind")](**fab)
    job = dict(config["job"])
    wl = JOBS[job.pop("kind")](topo.n_hosts, **job)
    return topo, wl


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
