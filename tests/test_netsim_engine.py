"""Tests for the generalized staged netsim engine.

* Golden equivalence: the variable-hop engine must reproduce the Table-1
  finish ticks bit-for-bit (constants in ``netsim_goldens``).
* Unit tests for the stage functions and share policies.
* Fat-tree link indexing / candidate-path correctness.
* End-to-end runs of the new topologies and collectives through the
  benchmark scenario registry.
"""
import jax
import numpy as np
import pytest

from repro.core.netsim import (SimParams, WorkloadBuilder, build_static,
                               link_domains, make_fat_tree, make_leaf_spine,
                               metrics, simulate)
from repro.core.netsim.simulator import wl_arrays
from repro.core.netsim import stages
from repro.core.netsim.stages import (make_ctx, init_state, select_routes,
                                      seg_global, wire_step)
from netsim_goldens import (GOLDEN_FLOWS_ECMP_BASE, GOLDEN_FLOWS_ECMP_SYM,
                            GOLDEN_JOB)


def _table1():
    topo = make_leaf_spine(32, 4, 4)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(32)), ring_size=8, chunk_bytes=1e6,
                   passes=2, barrier=False)
    return topo, b.build()


def test_golden_equivalence_table1():
    """The engine reproduces the Table-1 goldens bit-for-bit (sym on and
    off)."""
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64)
    base = simulate(topo, wl, cfg, routing="ecmp", seed=3)
    assert int(base.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_base"]
    assert np.asarray(base.finish_ticks).tolist() == GOLDEN_FLOWS_ECMP_BASE
    sym = simulate(topo, wl, cfg._replace(sym_on=True), routing="ecmp",
                   seed=3)
    assert int(sym.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_sym"]
    assert np.asarray(sym.finish_ticks).tolist() == GOLDEN_FLOWS_ECMP_SYM


@pytest.mark.slow
def test_golden_equivalence_balanced_and_pq():
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64)
    bal = simulate(topo, wl, cfg._replace(sym_on=True), routing="balanced",
                   seed=3)
    assert int(bal.job_finish_ticks[0]) == GOLDEN_JOB["balanced_sym"]
    pq = simulate(topo, wl, cfg._replace(pq_on=True), routing="ecmp", seed=3)
    assert int(pq.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_pq"]


# ----------------------------------------------------------- stage units
def _small_ctx(cfg=None, routing="balanced"):
    topo = make_leaf_spine(8, 2, 2)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                   passes=1)
    wl = b.build()
    cfg = cfg or SimParams(n_ticks=100, window=8, record_every=10)
    st = build_static(topo, wl, routing, seed=0, dt=cfg.dt, deploy=cfg.deploy)
    return topo, wl, cfg, make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)


def test_wire_step_encoding_monotone():
    sps, phase, nph = 6, 0, 1
    ws = [int(wire_step(c, sps, phase, nph)) for c in range(18)]
    assert ws == sorted(ws) and len(set(ws)) == len(ws)
    # segment index advances every sps steps
    assert int(seg_global(5, 6, 0, 1)) == 0 and int(seg_global(6, 6, 0, 1)) == 1
    # phase 1 of a 2-phase job interleaves after phase 0 of the same pass
    assert int(seg_global(0, 6, 1, 2)) == 1 and int(seg_global(6, 6, 0, 2)) == 2


def test_stage_starts_gates_on_ring_dependency():
    _, wl, cfg, ctx = _small_ctx()
    state = init_state(ctx, jax.random.PRNGKey(0))
    starts = stages.stage_starts(ctx, state, 0)
    # step 0 can start everywhere at tick 0
    assert bool(np.asarray(starts.can).all())
    assert np.asarray(starts.step_of)[:, 0].tolist() == [0] * wl.n_flows
    # step 1 is blocked until the predecessor's step-0 send makes progress
    state1 = state._replace(next_step=starts.next_step,
                            step_of=starts.step_of, sent=starts.sent)
    starts1 = stages.stage_starts(ctx, state1, 1)
    assert not bool(np.asarray(starts1.can).any())
    # completing the predecessor's chunk unblocks step 1
    state2 = state1._replace(sent=jax.numpy.full_like(starts.sent, 1e6))
    starts2 = stages.stage_starts(ctx, state2, 2)
    assert bool(np.asarray(starts2.can).all())


def test_stage_queues_red_profile():
    _, _, cfg, ctx = _small_ctx()
    cap = np.asarray(ctx.st.cap)
    offered = np.zeros_like(cap)
    offered[0] = cap[0] * 2.0          # 2x overload on one access link
    offered[-1] = 1e30                 # null link must stay empty
    q, p_red = stages.stage_queues(ctx, cfg, np.zeros_like(cap), offered)
    q = np.asarray(q)
    assert q[0] == pytest.approx(cap[0] * cfg.dt)
    assert q[-1] == 0.0
    # RED profile: 0 below kmin, pmax above kmax
    q2 = np.zeros_like(cap)
    q2[1] = cfg.red_kmax * 2
    _, p2 = stages.stage_queues(ctx, cfg, q2, np.zeros_like(cap))
    assert float(np.asarray(p2)[1]) == pytest.approx(cfg.red_pmax)
    assert float(np.asarray(p2)[0]) == 0.0


def test_select_routes_per_step_rehash():
    _, wl, cfg, ctx = _small_ctx(routing="balanced")
    # static: every instance of a flow uses the flow's route
    r_static = np.asarray(select_routes(ctx, np.zeros(ctx.FW, np.int32),
                                        per_step_ecmp=False))
    assert (r_static == np.repeat(np.asarray(ctx.st.routes), ctx.W,
                                  axis=0)).all()
    # per-step: routes always come from the flow's candidate table
    table = np.asarray(ctx.st.path_table)
    for step in (0, 1, 7):
        r = np.asarray(select_routes(
            ctx, np.full(ctx.FW, step, np.int32), per_step_ecmp=True))
        for i in range(0, ctx.FW, ctx.W):
            f = i // ctx.W
            assert any((r[i] == table[f, p]).all()
                       for p in range(table.shape[1]))
    # different steps re-roll at least one inter-ToR flow's path
    r0 = np.asarray(select_routes(ctx, np.zeros(ctx.FW, np.int32), True))
    r1 = np.asarray(select_routes(ctx, np.ones(ctx.FW, np.int32), True))
    assert (r0 != r1).any()


def test_share_policies_conserve_capacity():
    _, _, cfg, ctx = _small_ctx()
    state = init_state(ctx, jax.random.PRNGKey(0))
    starts = stages.stage_starts(ctx, state, 0)
    inst = stages.instance_view(ctx, starts, state, cfg.mtu, False)
    cap = np.asarray(ctx.st.cap)
    for name, fn in stages.SHARE_POLICIES.items():
        shr = fn(ctx, cfg, inst, 0)
        eff = np.asarray(shr.eff)
        assert (eff >= 0).all(), name
        # delivered load on any link never exceeds its capacity
        load = np.zeros_like(cap)
        np.add.at(load, np.asarray(inst.flat_links),
                  np.repeat(eff, ctx.H))
        assert (load[:-1] <= cap[:-1] * (1 + 1e-5)).all(), name


def test_wfq_weights_split_bottleneck():
    """Two single-flow jobs through one port: weight 3 gets ~3x bandwidth."""
    topo = make_leaf_spine(4, 2, 2)
    b = WorkloadBuilder()
    b.add_chain_job(pairs=[(0, 2)], steps=1, chunk_bytes=4e6)
    b.add_chain_job(pairs=[(1, 2)], steps=1, chunk_bytes=4e6)
    wl = b.build()
    # red_pmax=0 disables rate-control noise: shares are purely weighted-fair
    cfg = SimParams(n_ticks=8000, window=8, record_every=10,
                    share_policy="wfq", red_pmax=0.0)
    res = simulate(topo, wl, cfg, routing="balanced", seed=0,
                   job_weight=np.asarray([1.0, 3.0]))
    ft = np.asarray(res.finish_ticks).astype(float)
    assert ft[1] < ft[0]
    # heavy job saturates 3/4 of the port until it finishes ...
    t_heavy = 4e6 / (1.25e9 * 0.75) / cfg.dt
    assert ft[1] == pytest.approx(t_heavy, rel=0.05)
    # ... then the light job (1/4 share so far) takes the whole port
    rem = 4e6 - ft[1] * cfg.dt * 1.25e9 * 0.25
    t_light = ft[1] + rem / 1.25e9 / cfg.dt
    assert ft[0] == pytest.approx(t_light, rel=0.05)


# --------------------------------------------------- fat-tree link table
def test_fat_tree_link_indexing_disjoint_and_complete():
    ft = make_fat_tree(n_pods=2, tors_per_pod=2, spines_per_pod=2,
                       hosts_per_tor=2, n_cores=4)
    H, T, S, P, C = 8, 4, 2, 2, 4
    assert ft.n_hosts == H and ft.n_tors == T
    ids = []
    ids += [ft.acc_up(h) for h in range(H)]
    ids += [ft.acc_down(h) for h in range(H)]
    ids += [ft.uplink(t, s) for t in range(T) for s in range(S)]
    ids += [ft.downlink(p, s, p * 2 + tl) for p in range(P)
            for s in range(S) for tl in range(2)]
    ids += [ft.spine_up(p, s, s * 2 + j) for p in range(P)
            for s in range(S) for j in range(2)]
    ids += [ft.core_down(c, p) for c in range(C) for p in range(P)]
    ids = np.asarray(ids, np.int64)
    # the tiers tile [0, L) exactly once
    assert sorted(ids.tolist()) == list(range(ft.n_links))
    assert ft.link_switch.shape[0] == ft.n_links
    assert ft.switch_level.shape[0] == T + P * S + C


def test_fat_tree_candidate_paths_inter_pod():
    ft = make_fat_tree(n_pods=2, tors_per_pod=2, spines_per_pod=2,
                       hosts_per_tor=2, n_cores=4)
    paths, n_paths = ft.candidate_paths(np.asarray([0]), np.asarray([4]))
    assert int(n_paths[0]) == 4          # one candidate per core
    for c in range(4):
        s = c // ft.cores_per_spine
        expect = [ft.acc_up(0), ft.uplink(0, s), ft.spine_up(0, s, c),
                  ft.core_down(c, 1), ft.downlink(1, s, 2), ft.acc_down(4)]
        assert paths[0, c].tolist() == [int(x) for x in expect]
    # intra-pod inter-ToR: spine fan-out, core hops null-padded
    p2, n2 = ft.candidate_paths(np.asarray([0]), np.asarray([2]))
    assert int(n2[0]) == 2
    null = ft.n_links
    assert p2[0, 0].tolist() == [int(ft.acc_up(0)), int(ft.uplink(0, 0)),
                                 int(ft.downlink(0, 0, 1)), null, null,
                                 int(ft.acc_down(2))]


def test_link_domains_deploy_tiers():
    topo = make_leaf_spine(8, 2, 2)
    dom, D = link_domains(topo, "tor")
    assert D == 2
    assert dom[topo.acc_down(np.arange(8))].tolist() == [0, 0, 0, 0,
                                                         1, 1, 1, 1]
    assert int(dom[topo.uplink(1, 0)]) == 1
    assert int(dom[topo.downlink(0, 1)]) == D        # spine egress excluded
    assert int(dom[topo.acc_up(0)]) == D             # host NIC excluded
    dom_all, D_all = link_domains(topo, "all")
    assert D_all == 4
    assert int(dom_all[topo.downlink(1, 0)]) == 2 + 1   # spine 1 compacted
    dom_sp, D_sp = link_domains(topo, "spine")
    assert D_sp == 2
    assert int(dom_sp[topo.uplink(0, 0)]) == D_sp    # ToR egress excluded
    assert int(dom_sp[topo.downlink(1, 1)]) == 1
    with pytest.raises(ValueError):
        link_domains(topo, "nowhere")


# ------------------------------------------------------ workload builders
def test_max_segments_padded_and_validated():
    b = WorkloadBuilder(max_segments=5)
    b.add_ring_job(hosts=list(range(4)), ring_size=4, chunk_bytes=2e6,
                   passes=2)
    wl = b.build()
    assert wl.chunk_sched.shape == (1, 5)
    assert wl.chunk_sched[0].tolist() == [2e6] * 5   # padded with last value
    b2 = WorkloadBuilder(max_segments=1)
    b2.add_ring_job(hosts=list(range(4)), ring_size=4, chunk_bytes=2e6,
                    passes=2)
    with pytest.raises(ValueError):
        b2.build()


def test_halving_doubling_schedule_shape():
    b = WorkloadBuilder()
    b.add_halving_doubling_job(hosts=list(range(8)), chunk_bytes=8e6)
    wl = b.build()
    assert wl.n_phases[0] == 6                       # 2 * log2(8)
    assert wl.n_flows == 6 * 8                       # one slot per (node, phase)
    np.testing.assert_allclose(
        wl.chunk_sched[0], [4e6, 2e6, 1e6, 1e6, 2e6, 4e6])
    # every slot runs exactly one step per pass, self-gated
    assert (wl.steps_per_seg == 1).all()
    assert (wl.pred == np.arange(wl.n_flows)).all()


def test_ideal_cct_multi_phase():
    b = WorkloadBuilder()
    b.add_hierarchical_job(hosts=list(range(8)), group_size=4,
                           chunk_bytes=4e6)
    wl = b.build()
    # 3 steps x V/4 local RS + 2 steps x V/8 leader ring + 3 x V/4 local AG
    expect = (3 * 1e6 + 2 * 0.5e6 + 3 * 1e6) / 1.25e9
    assert metrics.ideal_cct(wl, 0, 1.25e9) == pytest.approx(expect)


# ----------------------------------------------- registry / end-to-end
def test_fat_tree_and_halving_doubling_through_registry():
    """Acceptance: 3-tier fat-tree + halving-doubling end-to-end via the
    scenario registry, finishing within 2x of the lockstep bound under
    balanced routing."""
    from benchmarks.common import build_scenario
    for name, kw in [
        ("fat_tree_ring", dict(chunk=5e5, passes=1)),
        ("fat_tree_halving_doubling", dict(chunk=1e6)),
        ("hierarchical_tor", dict(n_hosts=16, n_tors=2, n_spines=2,
                                  chunk=2e6, passes=1)),
    ]:
        built = build_scenario(name, **kw)
        res = jax.block_until_ready(
            simulate(built.topo, built.wl, built.cfg, routing="balanced",
                     seed=0))
        cct = metrics.cct_seconds(res, built.wl, built.cfg)[0]
        ideal = metrics.ideal_cct(built.wl, 0, 1.25e9)
        assert np.isfinite(cct), name
        assert cct < 2.0 * ideal + 1e-3, (name, cct, ideal)


def test_fat_tree_core_oversubscription_slows_inter_pod():
    from benchmarks.common import build_scenario
    ccts = {}
    # at os=8 each core link (8 host-loads over 2 cores at os=1) drops to
    # half a line rate, so inter-pod ring steps take ~2x
    for os_core in (1.0, 8.0):
        built = build_scenario("fat_tree_ring", chunk=5e5, passes=1,
                               core_oversubscription=os_core)
        res = simulate(built.topo, built.wl, built.cfg, routing="balanced",
                       seed=0)
        ccts[os_core] = metrics.cct_seconds(res, built.wl, built.cfg)[0]
    assert ccts[8.0] > ccts[1.0] * 1.4, ccts
