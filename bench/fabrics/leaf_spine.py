"""Two-tier leaf-spine fabric: ``n_hosts`` under ``n_tors`` ToRs, every
ToR wired to each of ``n_spines`` spines; host links at ``host_gbps``,
ToR-spine links at ``fabric_gbps``.

Reference link numbering: host->ToR, ToR->host, ToR t->spine s
(``t*S+s``), spine s->ToR t (``s*T+t``); candidate ``p`` goes through
spine ``p``.  Each ToR is a Symphony domain.
"""
import numpy as np

GBPS = 1e9 / 8.0          # bytes/s in one Gbit/s


def program(n_hosts, n_tors, n_spines, host_gbps, fabric_gbps):
    """The simulator's ``Topology``; its constructor takes the ToR-spine
    rate as an oversubscription factor."""
    from repro.core.netsim import make_leaf_spine

    return make_leaf_spine(
        n_hosts, n_tors, n_spines, host_gbps * GBPS,
        oversubscription=host_gbps * (n_hosts / n_tors) / n_spines
        / fabric_gbps)


def reference(n_hosts, n_tors, n_spines, host_gbps, fabric_gbps):
    from reference.build import Fabric

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
