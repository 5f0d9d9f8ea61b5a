"""One ring all-reduce job over every host: interleaved rings of
``ring`` hosts (ring g = hosts g, g+G, ...), ``passes`` passes of
``chunk_bytes`` chunks chained back to back with no barrier, so every
flow runs ``passes * 2 * (ring - 1)`` steps of one chunk each."""
import numpy as np


def program(n_hosts, ring, chunk_bytes, passes):
    """The simulator's ``Workload``."""
    from repro.core.netsim import WorkloadBuilder

    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk_bytes, passes=passes, barrier=False)
    return b.build()


def reference(n_hosts, dt, ring, chunk_bytes, passes):
    """The reference's job fields; nothing here is timed, so ``dt`` is
    unused."""
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
