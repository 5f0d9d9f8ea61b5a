"""Equivalence tests for the fused ``kernels/netsim_tick`` Pallas kernel.

The staged XLA engine is the golden reference: in interpret mode with
``segsum="scatter"`` the kernel must match it **bit-for-bit**, both
per-output on single ticks and tick-for-tick through whole runs — the
Table-1 golden finish ticks (``netsim_goldens``) must hold unchanged
under ``backend="pallas"``.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.netsim import (SimParams, WorkloadBuilder, build_static,
                               make_leaf_spine, simulate, simulate_grid)
from repro.core.netsim.simulator import wl_arrays
from repro.core.netsim.stages import (engine_tick, engine_tick_xla,
                                      init_state, make_ctx, resolve_backend,
                                      stage_starts)
from repro.kernels.netsim_tick import (fused_outputs_ref, fused_tick,
                                       engine_tick_fused)
from netsim_goldens import GOLDEN_JOB   # the pallas backend must match these


def _table1():
    topo = make_leaf_spine(32, 4, 4)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(32)), ring_size=8, chunk_bytes=1e6,
                   passes=2, barrier=False)
    return topo, b.build()


def _small():
    topo = make_leaf_spine(8, 2, 2)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=2e5,
                   passes=1, barrier=False)
    return topo, b.build()


def _assert_results_equal(a, b, what):
    for f in a._fields:
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f"{what}: {f}"


# ------------------------------------------------- single-tick, per-output
@pytest.mark.parametrize("variant", [
    dict(), dict(sym_on=True), dict(pq_on=True), dict(share_policy="pq")])
def test_kernel_outputs_bitwise_vs_stage_oracle(variant):
    """Every kernel output equals the stage-function oracle, bitwise, on a
    nontrivial mid-run state — including a sym-window epoch tick."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, **variant)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt, deploy=cfg.deploy)
    ctx = make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)
    state = init_state(ctx, jax.random.PRNGKey(0))
    # Both sides jitted: the kernel body always compiles as one XLA
    # computation, and an eager (op-by-op) oracle loses bitwise equality
    # to CPU fusion's FMA contraction.  Compiled-vs-compiled is the
    # configuration the engine actually runs in (everything under scan).
    run_kernel = jax.jit(lambda s, st_, t: fused_tick(ctx, cfg, s, st_, t))
    run_ref = jax.jit(
        lambda s, st_, t: fused_outputs_ref(ctx, cfg, s, st_, t))
    # ticks 0..29 cover cold start, active sharing, and three epoch
    # boundaries (sym_win_ticks=10: ticks 9, 19, 29)
    for tick in range(30):
        starts = stage_starts(ctx, state, tick)
        out = run_kernel(starts, state, jnp.int32(tick))
        ref = run_ref(starts, state, jnp.int32(tick))
        for f in out._fields:
            assert np.array_equal(np.asarray(getattr(out, f)),
                                  np.asarray(getattr(ref, f))), \
                f"tick {tick}: {f}"
        state, _ = engine_tick_xla(ctx, cfg, state, tick)


def test_kernel_onehot_segsum_allclose():
    """The dense one-hot segsum mode (the compiled-TPU shape of the
    reductions) reassociates adds: allclose, and int outputs exact."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, sym_on=True)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt, deploy=cfg.deploy)
    ctx = make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)
    state = init_state(ctx, jax.random.PRNGKey(0))
    scatter = jax.jit(
        lambda s, st_, t: fused_tick(ctx, cfg, s, st_, t, segsum="scatter"))
    onehot = jax.jit(
        lambda s, st_, t: fused_tick(ctx, cfg, s, st_, t, segsum="onehot"))
    for tick in range(12):
        starts = stage_starts(ctx, state, tick)
        a = scatter(starts, state, jnp.int32(tick))
        b = onehot(starts, state, jnp.int32(tick))
        for f in a._fields:
            x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            if x.dtype.kind == "i":
                assert np.array_equal(x, y), f"tick {tick}: {f}"
            else:
                np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5,
                                           err_msg=f"tick {tick}: {f}")
        state, _ = engine_tick_xla(ctx, cfg, state, tick)


# ------------------------------------------------ whole-run, tick-for-tick
@pytest.mark.parametrize("variant", [
    dict(), dict(sym_on=True), dict(pq_on=True), dict(share_policy="pq")])
def test_backend_pallas_matches_xla_run(variant):
    topo, wl = _small()
    cfg = SimParams(n_ticks=500, window=16, **variant)
    x = simulate(topo, wl, cfg, routing="ecmp", seed=3)
    p = simulate(topo, wl, cfg._replace(backend="pallas"), routing="ecmp",
                 seed=3)
    _assert_results_equal(x, p, f"pallas vs xla {variant}")


def test_backend_pallas_grid_matches_xla_grid():
    """The fused tick composes with the grid executor: knob lanes (sym and
    pq gates toggled) stay bitwise-equal to the XLA grid."""
    topo, wl = _small()
    base = SimParams(n_ticks=300, window=16)
    pts = [base, base._replace(sym_on=True), base._replace(pq_on=True)]
    x = simulate_grid(topo, wl, base.structure(),
                      [p.knobs() for p in pts], seeds=(0, 1))
    p = simulate_grid(topo, wl, base._replace(backend="pallas").structure(),
                      [p.knobs() for p in pts], seeds=(0, 1))
    _assert_results_equal(x, p, "pallas grid vs xla grid")


# -------------------------------------------------- dispatch and fallback
def test_wfq_drr_fall_back_to_xla_path():
    for policy in ("wfq", "drr"):
        cfg = SimParams(share_policy=policy, backend="pallas")
        assert resolve_backend(cfg) == "xla"
        topo, wl = _small()
        run = lambda c: simulate(topo, wl, c, routing="ecmp", seed=3)
        _assert_results_equal(
            run(SimParams(n_ticks=200, window=8, share_policy=policy)),
            run(SimParams(n_ticks=200, window=8, share_policy=policy,
                          backend="pallas")),
            f"{policy} fallback")
    assert resolve_backend(SimParams(backend="pallas")) == "pallas"
    assert resolve_backend(SimParams()) == "xla"


def test_unknown_backend_rejected():
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, backend="bogus")
    with pytest.raises(ValueError, match="backend"):
        simulate(topo, wl, cfg, routing="ecmp", seed=0)
    with pytest.raises(ValueError, match="backend"):
        simulate_grid(topo, wl, cfg.structure(), [cfg.knobs()])


# --------------------------------------------------------- golden chain
def test_golden_table1_pallas():
    """Acceptance: the pallas backend reproduces the golden finish
    ticks on Table 1 (ecmp, sym off/on) — the chain stays bit-for-bit."""
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64, backend="pallas")
    base = simulate(topo, wl, cfg, routing="ecmp", seed=3)
    assert int(base.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_base"]
    sym = simulate(topo, wl, cfg._replace(sym_on=True), routing="ecmp",
                   seed=3)
    assert int(sym.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_sym"]


@pytest.mark.slow
def test_golden_table1_pallas_balanced_and_pq():
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64, backend="pallas")
    bal = simulate(topo, wl, cfg._replace(sym_on=True), routing="balanced",
                   seed=3)
    assert int(bal.job_finish_ticks[0]) == GOLDEN_JOB["balanced_sym"]
    pq = simulate(topo, wl, cfg._replace(pq_on=True), routing="ecmp", seed=3)
    assert int(pq.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_pq"]


# ---------------------------------------------- tiled grid kernel (blk)
def _count_pallas_calls(jaxpr):
    """Recursively count pallas_call eqns (and collect their grids)."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params.get("grid_mapping"))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
                elif isinstance(v, (tuple, list)):
                    for u in v:
                        if hasattr(u, "jaxpr"):
                            walk(u.jaxpr)
    walk(jaxpr)
    return found


@pytest.mark.parametrize("blk", [16, 24, 4096])
def test_tiled_blk_sweep_matches_staged(blk):
    """blk in {divides FW=64, doesn't divide, >= FW (untiled)}: the tiled
    onehot grid kernel matches the staged engine through whole runs —
    int outputs exact, float series allclose (dense reductions and
    cross-block partial accumulation reassociate adds)."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=300, window=8)
    x = simulate(topo, wl, cfg._replace(sym_on=True), routing="ecmp", seed=3)
    t = simulate(topo, wl,
                 cfg._replace(sym_on=True, backend="pallas",
                              segsum="onehot", blk=blk),
                 routing="ecmp", seed=3)
    for f in x._fields:
        a, b = np.asarray(getattr(x, f)), np.asarray(getattr(t, f))
        if a.dtype.kind == "i":
            assert np.array_equal(a, b), f"blk={blk}: {f}"
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"blk={blk}: {f}")


def test_blk_requires_onehot():
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, backend="pallas", blk=16)
    with pytest.raises(ValueError, match="onehot"):
        simulate(topo, wl, cfg, routing="ecmp", seed=0)


# -------------------------------------------- multi-tick window (fusion)
@pytest.mark.parametrize("tw", [1, 5, 7])
def test_tick_window_sweep_matches_staged(tw):
    """tick_window in {1, divides record_every=20, doesn't divide}: the
    multi-tick window kernel stays bit-for-bit with the staged engine
    (the kernel body replays the stage functions per tick, so op order
    is identical)."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=300, window=8, record_every=20)
    for variant in (dict(), dict(sym_on=True), dict(pq_on=True)):
        x = simulate(topo, wl, cfg._replace(**variant), routing="ecmp",
                     seed=3)
        w = simulate(topo, wl,
                     cfg._replace(backend="pallas", tick_window=tw,
                                  **variant),
                     routing="ecmp", seed=3)
        _assert_results_equal(x, w, f"tick_window={tw} {variant}")


def test_tick_window_requires_pallas_backend():
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, tick_window=5)
    with pytest.raises(ValueError, match="pallas"):
        simulate(topo, wl, cfg, routing="ecmp", seed=0)
    # wfq falls back to the staged XLA path -> same rejection
    cfg = cfg._replace(backend="pallas", share_policy="wfq")
    with pytest.raises(ValueError, match="pallas"):
        simulate(topo, wl, cfg, routing="ecmp", seed=0)


def test_tick_window_combines_with_blk_tiling():
    """blk + tick_window combine: plan_tiling routes the config through
    the window kernel (tiling normalizes to None — windowing already
    amortizes the state traffic), and the onehot reductions there stay
    int-exact / float-allclose vs the staged engine."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=300, window=8, record_every=20, sym_on=True)
    x = simulate(topo, wl, cfg, routing="ecmp", seed=3)
    c = simulate(topo, wl,
                 cfg._replace(backend="pallas", segsum="onehot", blk=16,
                              tick_window=5),
                 routing="ecmp", seed=3)
    for f in x._fields:
        a, b = np.asarray(getattr(x, f)), np.asarray(getattr(c, f))
        if a.dtype.kind in "iub":
            assert np.array_equal(a, b), f"blk+tick_window: {f}"
        else:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"blk+tick_window: {f}")


def test_wfq_fallback_warns_once():
    from repro.core.netsim import stages

    topo, wl = _small()
    cfg = SimParams(n_ticks=40, window=8, backend="pallas",
                    share_policy="wfq")
    stages._FALLBACK_WARNED.discard("wfq")
    with pytest.warns(UserWarning, match="falls back"):
        simulate(topo, wl, cfg, routing="ecmp", seed=0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second resolve must stay silent
        assert resolve_backend(cfg) == "xla"


# ------------------------------------- lane batching: ONE kernel dispatch
def test_grid_lanes_dispatch_single_pallas_call():
    """A simulate_grid batch of 8 lanes through the tiled onehot kernel
    traces to exactly ONE pallas_call whose grid is lane-leading
    [lanes, sweeps, FW_blocks] — vmap batches the grid, it does not
    replicate the kernel."""
    from repro.core.netsim import simulator as sim

    topo, wl = _small()
    base = SimParams(n_ticks=40, window=8, backend="pallas",
                     segsum="onehot", blk=16)
    struct = base.structure()
    pts = [base._replace(sym_on=bool(i % 2)).knobs() for i in range(4)]
    from repro.core.netsim.params import stack_knobs
    knobs = stack_knobs(pts)
    st = build_static(topo, wl, "ecmp", seed=3, dt=base.dt,
                      deploy=base.deploy)
    wla = wl_arrays(wl, base.dt)
    st_stack = jax.tree.map(lambda x: jnp.stack([x, x]), st)
    keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])

    jx = jax.make_jaxpr(
        lambda s, kn, ky: sim._grid_impl(s, wla, struct, kn, ky))(
            st_stack, knobs, keys)
    calls = _count_pallas_calls(jx.jaxpr)
    assert len(calls) == 1, f"expected 1 pallas_call, got {len(calls)}"
    grid = calls[0].grid
    FW = wla.src.shape[0] * base.window
    nb = -(-FW // 16)
    assert grid[0] == 8, f"lane axis not leading: grid={grid}"   # 4 knobs x 2 seeds
    assert tuple(grid[1:]) == (4, nb), f"grid={grid}"


def test_window_kernel_single_dispatch_under_grid():
    """The multi-tick window kernel also batches to one pallas_call per
    scan body under an 8-lane grid."""
    from repro.core.netsim import simulator as sim
    from repro.core.netsim.params import stack_knobs

    topo, wl = _small()
    base = SimParams(n_ticks=40, window=8, record_every=20,
                     backend="pallas", tick_window=5)
    struct = base.structure()
    knobs = stack_knobs([base._replace(sym_on=bool(i % 2)).knobs()
                         for i in range(8)])
    st = build_static(topo, wl, "ecmp", seed=3, dt=base.dt,
                      deploy=base.deploy)
    wla = wl_arrays(wl, base.dt)
    st_stack = jax.tree.map(lambda x: x[None], st)
    keys = jax.random.PRNGKey(0)[None]

    jx = jax.make_jaxpr(
        lambda s, kn, ky: sim._grid_impl(s, wla, struct, kn, ky))(
            st_stack, knobs, keys)
    calls = _count_pallas_calls(jx.jaxpr)
    assert len(calls) == 1, f"expected 1 pallas_call, got {len(calls)}"


# --------------------------------------------- Mosaic-readiness (static)
def test_tiled_onehot_stablehlo_scatter_free_and_gather_free():
    """CI Mosaic gate: the tiled onehot kernel's lowering contains NO
    scatter ops AND NO gather ops — the dense segment reductions plus
    the iota-select null-link zeroing removed every vector scatter, and
    the packed per-block route/chunk/ECMP tables (streamed via BlockSpec
    with scalar-prefetched per-block valid counts) removed every gather —
    and the full 8-lane grid dispatch is a single pallas_call."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=40, window=8, sym_on=True)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt, deploy=cfg.deploy)
    ctx = make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)
    state = init_state(ctx, jax.random.PRNGKey(0))
    starts = stage_starts(ctx, state, 0)

    def tiled(s, st_, t):
        return fused_tick(ctx, cfg, s, st_, t, segsum="onehot", blk=16)

    batched = jax.vmap(tiled, in_axes=(None, None, 0))
    ticks = jnp.arange(8, dtype=jnp.int32)
    jx = jax.make_jaxpr(batched)(starts, state, ticks)
    assert len(_count_pallas_calls(jx.jaxpr)) == 1
    txt = jax.jit(batched).trace(starts, state, ticks).lower(
        lowering_platforms=("tpu",)).as_text()
    n_scatter = txt.count("stablehlo.scatter")
    assert n_scatter == 0, f"{n_scatter} scatter ops in tiled onehot HLO"
    n_gather = txt.count("stablehlo.gather") + txt.count("dynamic_gather")
    assert n_gather == 0, f"{n_gather} gather ops in tiled onehot HLO"


def test_golden_table1_tick_window_and_tiled():
    """Acceptance: the multi-tick window kernel (scatter, bit-for-bit),
    the tiled onehot grid kernel (allclose floats; finish ticks are
    ints), and the combined blk x tick_window config all land the seed
    golden finish ticks on Table 1."""
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64, backend="pallas")
    for c in (cfg._replace(tick_window=5),
              cfg._replace(segsum="onehot", blk=256),
              cfg._replace(segsum="onehot", blk=256, tick_window=5)):
        base = simulate(topo, wl, c, routing="ecmp", seed=3)
        assert int(base.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_base"]
        sym = simulate(topo, wl, c._replace(sym_on=True), routing="ecmp",
                       seed=3)
        assert int(sym.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_sym"]


@pytest.mark.slow
def test_golden_table1_tick_window_balanced_and_pq():
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64, backend="pallas",
                    tick_window=5)
    bal = simulate(topo, wl, cfg._replace(sym_on=True), routing="balanced",
                   seed=3)
    assert int(bal.job_finish_ticks[0]) == GOLDEN_JOB["balanced_sym"]
    pq = simulate(topo, wl, cfg._replace(pq_on=True), routing="ecmp", seed=3)
    assert int(pq.job_finish_ticks[0]) == GOLDEN_JOB["ecmp_pq"]


def test_window_kernel_bitwise_vs_window_ref():
    """Direct window-vs-oracle check on a nontrivial mid-run state: one
    engine_window_fused call equals n staged ticks, bitwise (both sides
    jitted — same contract as the single-tick oracle tests)."""
    from repro.kernels.netsim_tick import window_ref
    from repro.kernels.netsim_tick.ops import engine_window_fused

    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, sym_on=True, backend="pallas",
                    tick_window=5)
    st = build_static(topo, wl, "ecmp", seed=3, dt=cfg.dt, deploy=cfg.deploy)
    ctx = make_ctx(st, wl_arrays(wl, cfg.dt), cfg.window)
    from repro.core.netsim.params import merge_params
    struct, knobs = cfg.split()
    ecfg = merge_params(struct, knobs)
    state = init_state(ctx, jax.random.PRNGKey(0))
    # advance 20 ticks so queues/Symphony windows are warm
    for t in range(20):
        state, _ = engine_tick_xla(ctx, ecfg, state, t)
    run_k = jax.jit(lambda s, t: engine_window_fused(ctx, ecfg, s, t, 5))
    run_r = jax.jit(lambda s, t: window_ref(ctx, ecfg, s, t, 5))
    ks, ksmp = run_k(state, jnp.int32(20))
    rs, rsmp = run_r(state, jnp.int32(20))
    for f in ks._fields:
        assert np.array_equal(np.asarray(getattr(ks, f)),
                              np.asarray(getattr(rs, f))), f
    for i, (a, b) in enumerate(zip(ksmp, rsmp)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"sample[{i}]"
