"""A deployment's arrays for the reference engine.

A configuration file describes the fabric and the job in words of the
paper (hosts, switches per tier, link rates, ring size, chunk, passes).
The ``reference(...)`` functions of the kind files it names
(``bench/fabrics/<kind>.py`` returns a :class:`Fabric`,
``bench/jobs/<kind>.py`` a dict of :class:`Deployment` job fields) turn
that description into the flat arrays the reference engine steps: link
capacities, the Symphony domain of each link, the ECMP candidate paths
of every flow, and the ring schedule.  They use numpy alone and share no
code with the simulator; ``bench/tests`` checks that both sides describe
the same deployment.

The order of a flow's ECMP candidates is part of the deployment, since
the per-step hash picks ``hash % n_paths``.  Every path row has the
fabric's full hop count; hops a path does not use hold the null link
``L`` (no capacity limit, no Symphony domain).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from lib.cells import BENCH, kind


@dataclass(frozen=True)
class Fabric:
    n_hosts: int
    cap: np.ndarray        # [L+1] bytes/s, the null link last
    dom: np.ndarray        # [L+1] Symphony domain per link; D = none
    n_dom: int             # D
    hpt: int               # hosts per ToR
    paths_fn: object       # (src, dst) -> (paths [N,P,H], n_paths [N])


@dataclass(frozen=True)
class Deployment:
    """What the reference engine needs of one deployment."""
    cap: np.ndarray; dom: np.ndarray; D: int
    paths: np.ndarray; n_paths: np.ndarray; line_rate: np.ndarray
    src: np.ndarray; pred: np.ndarray; job: np.ndarray; phase: np.ndarray
    sps: np.ndarray; pass_steps: np.ndarray; total_steps: np.ndarray
    fstart: np.ndarray
    n_phases: np.ndarray; n_segs: np.ndarray; chunk: np.ndarray
    gap: np.ndarray; seg_ready0: np.ndarray; step_offset: np.ndarray
    trig_job: np.ndarray; trig_seg: np.ndarray; trig_delay: np.ndarray
    window: int

    @property
    def F(self):
        return int(self.src.shape[0])

    @property
    def J(self):
        return int(self.n_phases.shape[0])

    @property
    def L(self):
        return int(self.cap.shape[0]) - 1


def deployment(config: dict, base=BENCH) -> Deployment:
    """The reference's arrays for a configuration file's ``fabric``,
    ``job`` and ``engine`` sections, through the kind files under
    ``base`` (float values as float32, the precision the simulator
    states).

    A job kind's ``reference(n_hosts, dt, **job)`` returns the flow and
    job fields of :class:`Deployment` and ``dst``.  It may leave out
    ``fstart``, ``gap``, ``seg_ready0``, ``step_offset``, ``trig_job``,
    ``trig_seg`` and ``trig_delay``, which then hold 0 (-1, no trigger,
    for ``trig_job``).  Times are in ticks, ``np.round(seconds / dt)``,
    as the program's ``simulator.wl_arrays`` states them."""
    fab, args = kind("fabrics", config["fabric"], base)
    fabric = fab.reference(**args)
    job, args = kind("jobs", config["job"], base)
    dt = config["engine"]["dt"]
    jb = job.reference(fabric.n_hosts, dt, **args)
    F, J = len(jb["src"]), len(jb["n_phases"])
    jb = {"fstart": np.zeros(F, np.int64), "gap": np.zeros(J, np.int64),
          "seg_ready0": np.zeros(J, np.int64),
          "step_offset": np.zeros(F, np.int64), "trig_job": np.full(J, -1),
          "trig_seg": np.zeros(J, np.int64),
          "trig_delay": np.zeros(J, np.int64), **jb}
    src = jb.pop("src")
    paths, n_paths = fabric.paths_fn(src, jb.pop("dst"))
    f32 = lambda x: np.asarray(x, np.float32).astype(np.float64)
    cap = f32(fabric.cap)
    return Deployment(
        cap=cap, dom=fabric.dom, D=fabric.n_dom, paths=paths, n_paths=n_paths,
        line_rate=cap[src], src=src, chunk=f32(jb.pop("chunk")),
        window=int(config["engine"]["window"]), **jb)
