"""Three-tier multi-pod fat-tree: ``n_pods`` pods of ``tors_per_pod``
ToRs with ``hosts_per_tor`` hosts and ``spines_per_pod`` pod spines, and
``n_cores`` cores, each owned by one pod-spine position; host links at
``host_gbps``, ToR-spine links at ``edge_gbps``, spine-core links at
``core_gbps``.

Reference link numbering: host->ToR, ToR->host, ToR->pod spine, pod
spine->ToR, pod spine->core, core->pod spine; an inter-pod candidate
``c`` goes through core ``c`` and, on both sides, the pod spine
``c // (C/S)`` that owns it; an intra-pod candidate ``s`` through pod
spine ``s``.  Each ToR is a Symphony domain.
"""
import numpy as np

GBPS = 1e9 / 8.0          # bytes/s in one Gbit/s


def program(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
            host_gbps, edge_gbps, core_gbps):
    """The simulator's ``Topology``; its constructor takes the edge and
    core rates as oversubscription factors."""
    from repro.core.netsim import make_fat_tree

    return make_fat_tree(
        n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
        link_bps=host_gbps * GBPS,
        oversubscription=host_gbps * hosts_per_tor / spines_per_pod
        / edge_gbps,
        core_oversubscription=host_gbps * tors_per_pod * hosts_per_tor
        / n_cores / core_gbps)


def reference(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor, n_cores,
              host_gbps, edge_gbps, core_gbps):
    from reference.build import Fabric

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
