"""Plain constructors of a deployment for the reference engine.

A configuration file describes the fabric and the job in words of the
paper (hosts, switches per tier, link rates, ring size, chunk, passes).
These functions turn that description into the flat arrays the
reference engine steps: link capacities, the Symphony domain of each
link, the ECMP candidate paths of every flow, and the ring schedule.
They share no code with the simulator; ``bench/tests`` checks that both
sides describe the same deployment.

Link numbering (the order of ECMP candidates is part of the deployment,
since the per-step hash picks ``hash % n_paths``):

* leaf-spine: host->ToR, ToR->host, ToR t->spine s (``t*S+s``),
  spine s->ToR t (``s*T+t``); candidate ``p`` goes through spine ``p``.
* fat-tree: host->ToR, ToR->host, ToR->pod spine, pod spine->ToR,
  pod spine->core, core->pod spine; an inter-pod candidate ``c`` goes
  through core ``c`` and, on both sides, the pod spine ``c // (C/S)``
  that owns it; an intra-pod candidate ``s`` through pod spine ``s``.

Every path row has the fabric's full hop count; hops a path does not use
hold the null link ``L`` (no capacity limit, no Symphony domain).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

GBPS = 1e9 / 8.0          # bytes/s in one Gbit/s


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


def leaf_spine(n_hosts, n_tors, n_spines, host_gbps, fabric_gbps):
    H, T, S = n_hosts, n_tors, n_spines
    hpt = H // T
    L = 2 * H + 2 * T * S
    cap = np.full(L + 1, host_gbps * GBPS)
    cap[2 * H:L] = fabric_gbps * GBPS
    cap[L] = 1e30
    dom = np.full(L + 1, T, np.int64)          # ToR domains 0..T-1
    dom[H:2 * H] = np.arange(H) // hpt          # ToR -> host
    dom[2 * H:2 * H + T * S] = np.arange(T * S) // S   # ToR -> spine

    def paths_fn(src, dst):
        n = len(src)
        p = np.full((n, S, 4), L, np.int64)
        p[:, :, 0] = src[:, None]
        p[:, :, 3] = H + dst[:, None]
        ts, td = src // hpt, dst // hpt
        x = ts != td
        sp = np.arange(S)
        p[x, :, 1] = 2 * H + ts[x, None] * S + sp
        p[x, :, 2] = 2 * H + T * S + sp * T + td[x, None]
        return p, np.where(x, S, 1)

    return Fabric(H, cap, dom, T, hpt, paths_fn)


def fat_tree(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
             host_gbps, edge_gbps, core_gbps):
    Pd, Tp, S, C = n_pods, tors_per_pod, spines_per_pod, n_cores
    T, hpt = Pd * Tp, hosts_per_tor
    H = T * hpt
    cpg = C // S
    up = 2 * H                       # ToR -> spine
    down = up + T * S                # spine -> ToR
    s_core = down + T * S            # spine -> core
    core_dn = s_core + Pd * S * cpg  # core -> spine
    L = core_dn + C * Pd
    cap = np.full(L + 1, host_gbps * GBPS)
    cap[up:s_core] = edge_gbps * GBPS
    cap[s_core:L] = core_gbps * GBPS
    cap[L] = 1e30
    dom = np.full(L + 1, T, np.int64)
    dom[H:2 * H] = np.arange(H) // hpt
    dom[up:down] = np.arange(T * S) // S

    def paths_fn(src, dst):
        n = len(src)
        P = max(S, C)
        p = np.full((n, P, 6), L, np.int64)
        p[:, :, 0] = src[:, None]
        p[:, :, 5] = H + dst[:, None]
        ts, td = src // hpt, dst // hpt
        sp, dp = ts // Tp, td // Tp
        n_paths = np.ones(n, np.int64)
        for i in range(n):
            if ts[i] == td[i]:
                continue
            if sp[i] == dp[i]:
                for s in range(S):
                    p[i, s, 1] = up + ts[i] * S + s
                    p[i, s, 2] = down + (sp[i] * S + s) * Tp + td[i] % Tp
                n_paths[i] = S
            else:
                for c in range(C):
                    s = c // cpg
                    p[i, c, 1] = up + ts[i] * S + s
                    p[i, c, 2] = s_core + (sp[i] * S + s) * cpg + c % cpg
                    p[i, c, 3] = core_dn + c * Pd + dp[i]
                    p[i, c, 4] = down + (dp[i] * S + s) * Tp + td[i] % Tp
                n_paths[i] = C
        return p, n_paths

    return Fabric(H, cap, dom, T, hpt, paths_fn)


def ring_allreduce(n_hosts, ring, chunk_bytes, passes):
    """Interleaved rings of ``ring`` hosts (ring g = hosts g, g+G, ...),
    passes chained back to back with no barrier: every flow runs
    ``passes * 2 * (ring - 1)`` steps of one chunk each."""
    G = n_hosts // ring
    src, dst, pred = [], [], []
    for g in range(G):
        members = list(range(g, n_hosts, G))
        base = g * ring
        for j in range(ring):
            src.append(members[j])
            dst.append(members[(j + 1) % ring])
            pred.append(base + (j - 1) % ring)
    F = len(src)
    steps = passes * 2 * (ring - 1)
    return dict(src=np.array(src), dst=np.array(dst), pred=np.array(pred),
                job=np.zeros(F, np.int64), phase=np.zeros(F, np.int64),
                sps=np.full(F, steps), pass_steps=np.full(F, 2 * (ring - 1)),
                total_steps=np.full(F, steps), chunk=np.array([[chunk_bytes]]),
                n_phases=np.array([1]), n_segs=np.array([1]))


FABRICS = {"leaf_spine": leaf_spine, "fat_tree": fat_tree}
JOBS = {"ring_allreduce": ring_allreduce}


def deployment(config: dict) -> Deployment:
    """The reference's arrays for a configuration file's ``fabric``,
    ``job`` and ``engine`` sections (float values as float32, the
    precision the simulator states)."""
    fab = dict(config["fabric"])
    fabric = FABRICS[fab.pop("kind")](**fab)
    job = dict(config["job"])
    jb = JOBS[job.pop("kind")](fabric.n_hosts, **job)
    paths, n_paths = fabric.paths_fn(jb["src"], jb["dst"])
    F, J = len(jb["src"]), len(jb["n_phases"])
    f32 = lambda x: np.asarray(x, np.float32).astype(np.float64)
    cap = f32(fabric.cap)
    return Deployment(
        cap=cap, dom=fabric.dom, D=fabric.n_dom, paths=paths, n_paths=n_paths,
        line_rate=cap[jb["src"]], src=jb["src"], pred=jb["pred"],
        job=jb["job"], phase=jb["phase"], sps=jb["sps"],
        pass_steps=jb["pass_steps"], total_steps=jb["total_steps"],
        fstart=np.zeros(F, np.int64), n_phases=jb["n_phases"],
        n_segs=jb["n_segs"], chunk=f32(jb["chunk"]),
        gap=np.zeros(J, np.int64), seg_ready0=np.zeros(J, np.int64),
        step_offset=np.zeros(F, np.int64),
        trig_job=np.full(J, -1), trig_seg=np.zeros(J, np.int64),
        trig_delay=np.zeros(J, np.int64),
        window=int(config["engine"]["window"]))
