"""Property tests for the packed per-instance route tables.

`params.pack_route_tables` materializes dense ``[FW]``-leading copies of
every table the tick kernel used to *gather* from, so the tiled Pallas
kernel can BlockSpec-stream them and lower gather-free.  These tests pin
the packing contract: the packed slabs must round-trip **exactly** to the
reference ``table[index]`` gathers across ECMP fan-outs, topologies, and
non-dividing block tilings — plus the window-kernel state-donation and
the benchmark-trajectory dedupe contracts that ride on the same PR.

The staged engine's per-hop lookups go through flow-granular ``[F, P, H]``
tables (`stages.PathIndex`) and an exact plane pick: those tests pin the
pick to the per-instance gather it replaces, bit for bit, and the lowered
tick's gathers to at most F*P*H indices each.
"""
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.netsim import (SimParams, WorkloadBuilder, build_static,
                               make_fat_tree, make_leaf_spine)
from repro.core.netsim.params import (PackedTables, pack_route_tables,
                                      plan_tiling)
from repro.core.netsim.simulator import wl_arrays
from repro.core.netsim.stages import (init_state, instance_view, make_ctx,
                                      select_routes, stage_starts)


def _ring_wl(n_hosts, ring):
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=2e5, passes=1, barrier=False)
    return b.build()


def _leaf_spine(n_spines):
    return make_leaf_spine(8, 2, n_spines), _ring_wl(8, 4)


def _fat_tree_multipod():
    topo = make_fat_tree(n_pods=2, tors_per_pod=2, spines_per_pod=2,
                         hosts_per_tor=2)
    return topo, _ring_wl(topo.n_hosts, topo.n_hosts)


TOPOS = [lambda: _leaf_spine(1), lambda: _leaf_spine(2),
         lambda: _leaf_spine(4), _fat_tree_multipod]
TOPO_IDS = ["leaf_spine_p1", "leaf_spine_p2", "leaf_spine_p4",
            "fat_tree_multipod"]


def _ctx_for(build, window=8):
    topo, wl = build()
    cfg = SimParams(n_ticks=100, window=window)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt,
                      deploy=cfg.deploy)
    return st, make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window), cfg


# ------------------------------------------- packing == reference gathers
@pytest.mark.parametrize("build", TOPOS, ids=TOPO_IDS)
def test_packed_tables_match_reference_gathers(build):
    """Every packed slab equals the gather it replaces, bitwise, on the
    real inst_flow/inst_job layout (row f*W + w holds flow f's table)."""
    st, ctx, _ = _ctx_for(build)
    t = ctx.tables
    fl = np.asarray(ctx.inst_flow)
    jb = np.asarray(ctx.inst_job)
    ref = {
        "routes": np.asarray(st.routes)[fl],
        "route_dom": np.asarray(st.link_dom[st.routes])[fl],
        "cand": np.asarray(st.path_table)[fl],
        "cand_dom": np.asarray(st.link_dom[st.path_table])[fl],
        "n_paths": np.asarray(st.n_paths)[fl],
        "chunk": np.asarray(ctx.wl.chunk_sched)[jb],
    }
    for f in PackedTables._fields:
        assert np.array_equal(np.asarray(getattr(t, f)), ref[f]), f


def test_ecmp_fanout_coverage():
    """The parametrized topologies really cover P in {1, 2, 4}."""
    fanouts = set()
    for build in TOPOS:
        st, _, _ = _ctx_for(build)
        fanouts.add(int(st.path_table.shape[1]))
    assert {1, 2, 4} <= fanouts, f"P coverage only {sorted(fanouts)}"


@pytest.mark.parametrize("build", [lambda: _leaf_spine(4),
                                   _fat_tree_multipod],
                         ids=["leaf_spine_p4", "fat_tree_multipod"])
def test_iota_select_matches_candidate_gather(build):
    """The kernel's candidate-plane iota-select over the streamed slab
    equals the ``path_table[inst, choice]`` gather it replaced, for
    arbitrary in-range per-instance choices."""
    from repro.kernels.netsim_tick.kernel import _onehot_plane

    st, ctx, _ = _ctx_for(build)
    t = ctx.tables
    FW = ctx.FW
    rng = np.random.default_rng(7)
    n_p = np.asarray(t.n_paths)
    choice = jnp.asarray(rng.integers(0, 2**31 - 1, FW) % n_p, jnp.int32)
    sel = np.asarray(_onehot_plane(t.cand, choice))
    sel_dom = np.asarray(_onehot_plane(t.cand_dom, choice))
    fl = np.asarray(ctx.inst_flow)
    ch = np.asarray(choice)
    assert np.array_equal(sel, np.asarray(st.path_table)[fl, ch])
    assert np.array_equal(sel_dom,
                          np.asarray(st.link_dom[st.path_table])[fl, ch])


# ------------------------------ flow-granular lookups == per-instance ones
def _random_view(ctx, per_step_ecmp, seed=11):
    """The instance view over random window slots, ``-1`` (empty) among
    them, and random completion counters."""
    rng = np.random.default_rng(seed)
    state = init_state(ctx, jax.random.PRNGKey(0))
    step_of = rng.integers(-1, 40, (ctx.F, ctx.W)).astype(np.int32)
    step_of[rng.random((ctx.F, ctx.W)) < 0.25] = -1
    state = state._replace(done_upto=jnp.asarray(
        rng.integers(0, 20, ctx.F), jnp.int32))
    starts = stage_starts(ctx, state, 0)._replace(
        step_of=jnp.asarray(step_of),
        sent=jnp.asarray(rng.random((ctx.F, ctx.W)) * 3e5, jnp.float32))
    return instance_view(ctx, starts, state, 1000.0, per_step_ecmp)


def _assert_lookups_match_gathers(st, ctx, per_step_ecmp):
    inst = _random_view(ctx, per_step_ecmp)
    iroute = select_routes(ctx, inst.istep, per_step_ecmp)
    dj = st.link_dom[iroute] * ctx.J + ctx.inst_job[:, None]
    assert np.array_equal(np.asarray(inst.iroute), np.asarray(iroute))
    assert np.array_equal(np.asarray(inst.dj), np.asarray(dj))
    rng = np.random.default_rng(5)
    DJ = (ctx.D + 1) * ctx.J
    x_dj = jnp.asarray(rng.integers(-2**31, 2**31 - 1, DJ), jnp.int32)
    x_link = rng.standard_normal(ctx.L + 1).astype(np.float32)
    x_link[:3] = [np.nan, -0.0, np.inf]
    x_link = jnp.asarray(x_link)
    assert np.array_equal(np.asarray(inst.on_path(x_dj, dj=True)),
                          np.asarray(x_dj[dj]))
    # compare bit patterns: NaN payloads and the sign of zero included
    assert np.array_equal(np.asarray(inst.on_path(x_link)).view(np.uint32),
                          np.asarray(x_link[iroute]).view(np.uint32))
    return inst


@pytest.mark.parametrize("per_step_ecmp", [True, False],
                         ids=["per_step_ecmp", "static_routes"])
@pytest.mark.parametrize("build", TOPOS, ids=TOPO_IDS)
def test_on_path_matches_per_instance_gather(build, per_step_ecmp):
    """``InstView.on_path`` gathers once per (flow, candidate, hop) and
    picks each instance's plane; the result is bitwise the per-instance
    ``x[iroute]`` / ``x[dj]`` gather, for int and float arrays."""
    st, ctx, _ = _ctx_for(build)
    assert int(st.path_table.shape[1]) < ctx.W
    inst = _assert_lookups_match_gathers(st, ctx, per_step_ecmp)
    P = int(st.path_table.shape[1]) if per_step_ecmp else 1
    assert inst.paths.links.shape == (ctx.F, P, ctx.H)
    assert (inst.choice is None) == (not per_step_ecmp)


def test_on_path_matches_per_instance_gather_when_window_is_narrow():
    """With fewer window slots than candidates (W < P) the flow tables
    gather more indices than the instances would, and the pick still
    equals the per-instance gather, bit for bit."""
    st, ctx, _ = _ctx_for(lambda: _leaf_spine(4), window=2)
    assert ctx.W < int(st.path_table.shape[1])
    _assert_lookups_match_gathers(st, ctx, True)


def _gathers_by_scope(module):
    """``[(innermost netsim.* scope or None, indices gathered)]`` for every
    ``stablehlo.gather`` of a lowered module: an index per result element
    outside the gather's offset (slice) dimensions."""
    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for o in block.operations:
                    yield o
                    yield from walk(o)

    out = []
    for o in walk(module.operation):
        if o.name != "stablehlo.gather":
            continue
        scopes = re.findall(r"netsim\.[a-z_]+", str(o.location))
        m = re.search(r"offset_dims = \[([0-9, ]*)\]",
                      str(o.attributes["dimension_numbers"]))
        offset = {int(d) for d in m.group(1).split(",")} if m else set()
        shape = list(o.results[0].type.shape)
        n = int(np.prod([d for i, d in enumerate(shape) if i not in offset]))
        out.append((scopes[-1] if scopes else None, n))
    return out


@pytest.mark.parametrize("scenario, over", [
    ("table1_ring", {}), ("fat_tree_multipod", {"n_hosts": 512})],
    ids=["table1", "multipod512"])
def test_tick_gathers_at_most_flow_candidate_hop_indices(scenario, over):
    """One lane's staged XLA tick, lowered at the benchmark's widths with
    traced knobs (so the Symphony marking and the ``pq_on`` branch are in
    the program): no gather in the instance-view, share, marking or
    Symphony stage reads more than F*P*H indices, and the per-hop lookups
    read exactly that many."""
    from benchmarks.common import build_scenario, knob_grid, sweep_axes_for
    from repro.core.netsim.params import grid_from_params, merge_params
    from repro.core.netsim.stages import engine_tick_xla

    built = build_scenario(scenario, **over)
    struct, knobs = grid_from_params(
        knob_grid(built.cfg, sweep_axes_for(scenario))[:1])
    assert struct.backend == "xla" and struct.per_step_ecmp
    knobs = jax.tree.map(lambda x: x[0], knobs)
    st = build_static(built.topo, built.wl, "ecmp", seed=0, dt=struct.dt,
                      deploy=struct.deploy)
    wla = wl_arrays(built.wl, struct.dt)

    def tick(st, kn, key):
        ctx = make_ctx(st, wla, struct.window)
        return engine_tick_xla(ctx, merge_params(struct, kn),
                               init_state(ctx, key), jnp.int32(3))

    module = jax.jit(tick).lower(st, knobs, jax.random.PRNGKey(0)) \
        .compiler_ir("stablehlo")
    F, P, H = (int(d) for d in st.path_table.shape)
    assert P < struct.window
    staged = {"netsim.instance_view", "netsim.share", "netsim.marking",
              "netsim.symphony"}
    counts = [(s, n) for s, n in _gathers_by_scope(module) if s in staged]
    assert max(n for _, n in counts) == F * P * H, counts
    per_hop = sorted(s for s, n in counts if n == F * P * H)
    # path_min + pq's two; p_red + the three Symphony rows; stepmin
    assert per_hop == sorted(["netsim.share"] * 3 + ["netsim.marking"] * 4
                             + ["netsim.symphony"]), per_hop


@pytest.mark.parametrize("blk", [24, 40])
def test_edge_padded_blocks_reconstruct(blk):
    """Non-dividing blk: the edge-padded slab, sliced block-by-block and
    masked by the scalar-prefetched valid counts, reconstructs every
    packed table exactly (edge padding never invents out-of-range rows
    in the valid region)."""
    from repro.kernels.netsim_tick.kernel import _edge_pad

    st, ctx, _ = _ctx_for(lambda: _leaf_spine(2))
    FW = ctx.FW
    nb = -(-FW // blk)
    assert FW % blk != 0, "want a non-dividing blk for this test"
    nvalid = [min(blk, FW - i * blk) for i in range(nb)]
    for f in PackedTables._fields:
        x = np.asarray(getattr(ctx.tables, f))
        padded = np.asarray(_edge_pad(jnp.asarray(x), nb * blk - FW))
        got = np.concatenate([padded[i * blk: i * blk + nvalid[i]]
                              for i in range(nb)])
        assert np.array_equal(got, x), f"{f} blk={blk}"


def test_plan_tiling_contract():
    st, ctx, _ = _ctx_for(lambda: _leaf_spine(2))
    FW = ctx.FW
    assert plan_tiling(FW, None, "scatter", 1) is None
    assert plan_tiling(FW, 16, "onehot", 1) == 16
    # tick_window > 1 routes through the window kernel: tiling normalizes
    assert plan_tiling(FW, 16, "onehot", 5) is None
    # blk >= FW normalizes to untiled
    assert plan_tiling(FW, FW, "onehot", 1) is None
    with pytest.raises(ValueError, match="onehot"):
        plan_tiling(FW, 16, "scatter", 1)
    with pytest.raises(ValueError, match="blk"):
        plan_tiling(FW, 0, "onehot", 1)


# ------------------------------------------------ window state donation
def test_window_kernel_donates_state():
    """The multi-tick window dispatch aliases all N_STATE carried state
    inputs to their same-shaped outputs, so a record period of windows
    updates state in place instead of copying it once per window."""
    from repro.core.netsim.params import merge_params
    from repro.kernels.netsim_tick.ops import engine_window_fused
    from repro.kernels.netsim_tick.window import N_STATE

    topo, wl = _leaf_spine(2)[0], _ring_wl(8, 4)
    cfg = SimParams(n_ticks=100, window=8, sym_on=True, backend="pallas",
                    tick_window=5)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt,
                      deploy=cfg.deploy)
    ctx = make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)
    struct, knobs = cfg.split()
    ecfg = merge_params(struct, knobs)
    state = init_state(ctx, jax.random.PRNGKey(0))

    jx = jax.make_jaxpr(
        lambda s, t: engine_window_fused(ctx, ecfg, s, t, 5))(
            state, jnp.int32(0))
    aliases = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                aliases.append(eqn.params.get("input_output_aliases"))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)

    walk(jx.jaxpr)
    assert len(aliases) == 1, f"expected 1 pallas_call, got {len(aliases)}"
    got = dict(aliases[0])
    assert got == {i: i for i in range(N_STATE)}, got


# ------------------------------------------- benchmark trajectory dedupe
def test_bench_trajectory_dedupe_by_sha_mode_variant(tmp_path, monkeypatch):
    """Re-running netsim_perf on the same commit replaces that commit's
    trajectory entries (per variant) instead of appending duplicates;
    different variants and shas coexist, and legacy entries without a
    variant field read as pallas_tuned."""
    from benchmarks import netsim_perf as npf

    bench = tmp_path / "BENCH_netsim.json"
    legacy = {"sha": "old1", "mode": "quick", "ticks_per_s": 10}
    bench.write_text(json.dumps(
        {"schema": npf.BENCH_SCHEMA, "trajectory": [legacy]}))
    monkeypatch.setattr(npf, "BENCH_FILE", bench)
    monkeypatch.setattr(npf, "_git_sha", lambda: "abc1234")
    monkeypatch.setattr(npf, "_mode", lambda: "quick")
    result = {"grid_lanes": 16,
              "backends": {"xla": {"ticks_per_s": 100},
                           "pallas_tuned": {"ticks_per_s": 90},
                           "pallas_gatherfree": {"ticks_per_s": 80}}}

    data = npf.write_bench(result)
    traj = data["trajectory"]
    assert len(traj) == 3            # legacy + tuned + gatherfree
    assert traj[0] == legacy         # other shas untouched
    key = {(e["sha"], e.get("variant", "pallas_tuned")) for e in traj}
    assert ("abc1234", "pallas_tuned") in key
    assert ("abc1234", "pallas_gatherfree") in key

    # same sha+mode+variant again: replaced in place, not duplicated
    result["backends"]["pallas_gatherfree"]["ticks_per_s"] = 85
    traj = npf.write_bench(result)["trajectory"]
    assert len(traj) == 3
    gf = [e for e in traj if e.get("variant") == "pallas_gatherfree"]
    assert len(gf) == 1 and gf[0]["ticks_per_s"] == 85

    # a legacy pallas_tuned entry on the SAME sha is replaced too (the
    # missing variant field reads as pallas_tuned)
    legacy_same = {"sha": "abc1234", "mode": "quick", "ticks_per_s": 1}
    data = json.loads(bench.read_text())
    data["trajectory"].append(legacy_same)
    bench.write_text(json.dumps(data))
    traj = npf.write_bench(result)["trajectory"]
    tuned = [e for e in traj
             if e["sha"] == "abc1234"
             and e.get("variant", "pallas_tuned") == "pallas_tuned"]
    assert len(tuned) == 1 and tuned[0]["ticks_per_s"] == 90
