"""Three tenants on one fabric, a job kind for the tests: a barriered ring
over the even hosts, ``len(chunks)`` passes of per-pass chunks with a
compute gap of ``gap_s`` before each; a one-pass ring over the odd hosts,
released ``delay_s`` after the first ring's first collective; and a ring
over every host that arrives at ``start_s`` and chains as many passes
with no barrier between them."""
import numpy as np


def program(n_hosts, chunks, gap_s, delay_s, start_s):
    """The simulator's ``Workload``."""
    from repro.core.netsim import WorkloadBuilder

    even, odd = list(range(0, n_hosts, 2)), list(range(1, n_hosts, 2))
    b = WorkloadBuilder()
    b.add_ring_job(hosts=even, ring_size=len(even), chunk_bytes=chunks,
                   passes=len(chunks), compute_gap=gap_s)
    b.add_ring_job(hosts=odd, ring_size=len(odd), chunk_bytes=chunks[0])
    b.set_trigger(1, after_job=0, collectives=1, delay=delay_s)
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=n_hosts,
                   chunk_bytes=chunks[-1], passes=len(chunks), barrier=False,
                   start_time=start_s)
    return b.build()


def reference(n_hosts, dt, chunks, gap_s, delay_s, start_s):
    """The reference's job fields, times in ticks of ``dt``."""
    rings = [list(range(0, n_hosts, 2)), list(range(1, n_hosts, 2)),
             list(range(n_hosts))]
    passes = [len(chunks), 1, 1]     # the unbarriered ring is one segment
    chained = [1, 1, len(chunks)]    # passes in one segment
    segs = [list(chunks), [chunks[0]], [chunks[-1]]]
    src, dst, pred, job, sps, pass_steps = [], [], [], [], [], []
    for j, hosts in enumerate(rings):
        n, base = len(hosts), len(src)
        src += hosts
        dst += hosts[1:] + hosts[:1]
        pred += [base + (i - 1) % n for i in range(n)]
        job += [j] * n
        sps += [chained[j] * 2 * (n - 1)] * n
        pass_steps += [2 * (n - 1)] * n
    S = max(map(len, segs))
    chunk = np.array([c + [c[-1]] * (S - len(c)) for c in segs])
    ticks = lambda s: int(np.round(s / dt))
    sps, job = np.array(sps), np.array(job)
    return dict(
        src=np.array(src), dst=np.array(dst), pred=np.array(pred), job=job,
        phase=np.zeros(len(src), np.int64), sps=sps,
        pass_steps=np.array(pass_steps),
        total_steps=sps * np.array(passes)[job], chunk=chunk,
        n_phases=np.ones(3, np.int64), n_segs=np.array(passes),
        gap=np.array([ticks(gap_s), 0, 0]),
        seg_ready0=np.array([0, 0, ticks(start_s)]),
        trig_job=np.array([-1, 0, -1]), trig_seg=np.array([0, 1, 0]),
        trig_delay=np.array([0, ticks(delay_s), 0]))
