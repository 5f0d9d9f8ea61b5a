"""Tests for the SimStructure/RuntimeKnobs split and the batched grid
executor.

* vmap-consistency: ``simulate_grid`` slices are bitwise-equal to
  per-point ``simulate`` calls, and ``simulate_seeds`` to per-seed calls.
* compile discipline: a >=16-point knob grid traces the engine exactly
  once, per-point knob changes never retrace, chunking doesn't retrace.
* the SimParams facade: split/merge round-trip, legacy simulate_core
  call form, structural-mismatch rejection.
* the drr share policy and its registry selection.
* benchmark cache keying (overrides hash + schema invalidation) and the
  placement of the JAX compilation cache.
"""
import jax
import numpy as np
import pytest

from repro.core.netsim import (SHARE_POLICIES, SimParams, WorkloadBuilder,
                               core_trace_count, grid_from_params,
                               make_leaf_spine, merge_params, metrics,
                               simulate, simulate_grid, simulate_seeds,
                               stack_knobs)
from repro.core.netsim.simulator import build_static, wl_arrays
from repro.core.netsim import simulate_core


@pytest.fixture(scope="module")
def small():
    topo = make_leaf_spine(8, 2, 2)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                   passes=1)
    return topo, b.build()


def _grid16(cfg: SimParams) -> list[SimParams]:
    """16 knob points spanning both gates and two Symphony knob axes."""
    out = []
    for sym in (False, True):
        for pq in (False, True):
            for tau in (0.2, 0.25):
                for k in (1e-2, 3e-2):
                    out.append(cfg._replace(
                        sym_on=sym, pq_on=pq,
                        sym=cfg.sym._replace(tau=tau, k=k)))
    return out

# ------------------------------------------------------- vmap consistency
def test_grid_bitwise_equals_per_point(small):
    """Acceptance: grid output slices == per-point simulate, bitwise, and
    the whole 16-point grid compiles the engine exactly once."""
    topo, wl = small
    cfg = SimParams(n_ticks=1500, window=8, record_every=10)
    cfgs = _grid16(cfg)
    assert len(cfgs) >= 16
    seeds = [0, 1]
    struct, knobs = grid_from_params(cfgs)

    c0 = core_trace_count()
    res = simulate_grid(topo, wl, struct, knobs, seeds, routing="ecmp")
    assert core_trace_count() - c0 == 1, "grid must be ONE compile"

    for i in (0, 3, 7, 10, 15):          # spot-check across the grid
        for j, seed in enumerate(seeds):
            one = simulate(topo, wl, cfgs[i], routing="ecmp", seed=seed)
            assert np.array_equal(np.asarray(res.finish_ticks)[i, j],
                                  np.asarray(one.finish_ticks)), (i, seed)
            assert np.array_equal(np.asarray(res.job_finish_ticks)[i, j],
                                  np.asarray(one.job_finish_ticks))
            assert np.array_equal(np.asarray(res.ts_throughput)[i, j],
                                  np.asarray(one.ts_throughput))
            assert np.array_equal(np.asarray(res.ts_alpha_max)[i, j],
                                  np.asarray(one.ts_alpha_max))


def test_grid_chunking_matches_unchunked(small):
    topo, wl = small
    cfg = SimParams(n_ticks=800, window=8, record_every=10)
    cfgs = [cfg._replace(sym_on=True, sym=cfg.sym._replace(k=k))
            for k in (1e-3, 3e-3, 1e-2, 3e-2, 1e-1)]
    struct, knobs = grid_from_params(cfgs)
    full = simulate_grid(topo, wl, struct, knobs, [0], routing="ecmp")
    chunked = simulate_grid(topo, wl, struct, knobs, [0], routing="ecmp",
                            chunk_knobs=2)   # 5 points -> 2+2+2 padded
    for a, b in zip(full, chunked):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_grid_chunking_prime_grid_partial_chunk(small):
    """Regression for the partial-final-chunk path: a prime-sized grid
    (K=7) never divides evenly, so every chunk_knobs in 2..6 ends with a
    ragged chunk that the executor pads by repeating the final knob point
    and slices back.  The padded lanes must not leak: every chunking must
    be bitwise-identical to the unchunked dispatch, without re-tracing."""
    topo, wl = small
    cfg = SimParams(n_ticks=600, window=8, record_every=10)
    ks = (1e-3, 2e-3, 3e-3, 5e-3, 1e-2, 3e-2, 1e-1)     # K = 7, prime
    cfgs = [cfg._replace(sym_on=True, sym=cfg.sym._replace(k=k))
            for k in ks]
    struct, knobs = grid_from_params(cfgs)
    full = simulate_grid(topo, wl, struct, knobs, [0, 1], routing="ecmp")
    for chunk in (2, 3, 4, 5, 6):
        c0 = core_trace_count()
        part = simulate_grid(topo, wl, struct, knobs, [0, 1], routing="ecmp",
                             chunk_knobs=chunk)
        # a chunk size is a new lane-axis shape -> at most ONE engine
        # trace, amortized over all chunks (the ragged final chunk is
        # padded to the same shape, so it reuses the compilation)
        assert core_trace_count() - c0 <= 1, chunk
        for a, b in zip(full, part):
            assert np.array_equal(np.asarray(a), np.asarray(b)), chunk
    c0 = core_trace_count()
    simulate_grid(topo, wl, struct, knobs, [0, 1], routing="ecmp",
                  chunk_knobs=3)
    assert core_trace_count() == c0, "repeated chunking must not re-trace"


def test_simulate_seeds_consistent_with_simulate(small):
    topo, wl = small
    cfg = SimParams(n_ticks=1500, window=8, record_every=10, sym_on=True)
    seeds = [0, 2, 5]
    batch = simulate_seeds(topo, wl, cfg, "ecmp", seeds)
    for j, seed in enumerate(seeds):
        one = simulate(topo, wl, cfg, routing="ecmp", seed=seed)
        assert np.array_equal(np.asarray(batch.finish_ticks)[j],
                              np.asarray(one.finish_ticks)), seed
        assert np.array_equal(np.asarray(batch.ts_throughput)[j],
                              np.asarray(one.ts_throughput)), seed


def test_knob_change_does_not_retrace(small):
    topo, wl = small
    cfg = SimParams(n_ticks=600, window=8, record_every=10)
    simulate(topo, wl, cfg, routing="ecmp", seed=0)   # prime the cache
    c0 = core_trace_count()
    for kmin, pmax, sym in [(40e3, 0.1, True), (60e3, 0.3, False),
                            (50e3, 0.2, True)]:
        simulate(topo, wl,
                 cfg._replace(red_kmin=kmin, red_pmax=pmax, sym_on=sym),
                 routing="ecmp", seed=0)
    assert core_trace_count() == c0, "knob values must not recompile"
    # a structural change DOES recompile
    simulate(topo, wl, cfg._replace(record_every=20), routing="ecmp", seed=0)
    assert core_trace_count() == c0 + 1


# ----------------------------------------------------------------- facade
def test_split_merge_roundtrip():
    cfg = SimParams(n_ticks=42, red_pmax=0.3, sym_on=True, pq_on=False,
                    share_policy="wfq", deploy="spine")
    struct, knobs = cfg.split()
    assert struct.n_ticks == 42 and struct.share_policy == "wfq"
    assert struct.deploy == "spine"
    merged = merge_params(struct, knobs)
    assert merged.n_ticks == 42
    assert float(merged.red_pmax) == pytest.approx(0.3)
    assert int(merged.sym_on) == 1 and int(merged.pq_on) == 0
    assert float(merged.sym.tau) == pytest.approx(cfg.sym.tau)


def test_legacy_simulate_core_signature(small):
    topo, wl = small
    cfg = SimParams(n_ticks=600, window=8, record_every=10)
    st = build_static(topo, wl, "balanced", seed=0, dt=cfg.dt,
                      deploy=cfg.deploy)
    legacy = simulate_core(st, wl_arrays(wl, cfg.dt), cfg,
                           jax.random.PRNGKey(0))
    struct, knobs = cfg.split()
    new = simulate_core(st, wl_arrays(wl, cfg.dt), struct, knobs,
                        jax.random.PRNGKey(0))
    assert np.array_equal(np.asarray(legacy.finish_ticks),
                          np.asarray(new.finish_ticks))


def test_grid_rejects_structural_mismatch(small):
    cfg = SimParams(n_ticks=600, window=8)
    with pytest.raises(ValueError, match="static structure"):
        grid_from_params([cfg, cfg._replace(window=16)])
    with pytest.raises(ValueError, match="empty"):
        grid_from_params([])


def test_stack_knobs_leading_axis():
    cfg = SimParams()
    ks = stack_knobs([cfg._replace(red_pmax=p).knobs() for p in (0.1, 0.2)])
    assert ks.red_pmax.shape == (2,)
    assert ks.sym.tau.shape == (2,)
    np.testing.assert_allclose(np.asarray(ks.red_pmax), [0.1, 0.2])


def test_pq_on_conflict_still_rejected(small):
    topo, wl = small
    cfg = SimParams(n_ticks=200, window=8, pq_on=True, share_policy="wfq")
    with pytest.raises(ValueError, match="pq_on"):
        simulate(topo, wl, cfg, routing="balanced", seed=0)
    # the grid executor enforces the same rule (a pq point would silently
    # override the wfq base policy at runtime otherwise)
    base = SimParams(n_ticks=200, window=8, share_policy="wfq")
    struct, knobs = grid_from_params([base, base._replace(pq_on=True)])
    with pytest.raises(ValueError, match="pq_on"):
        simulate_grid(topo, wl, struct, knobs, [0], routing="balanced")


def test_pq_gate_matches_pq_policy(small):
    """The traced pq_on gate must reproduce the static pq policy exactly."""
    topo, wl = small
    cfg = SimParams(n_ticks=1500, window=8, record_every=10)
    gate = simulate(topo, wl, cfg._replace(pq_on=True), "ecmp", seed=3)
    static = simulate(topo, wl, cfg._replace(share_policy="pq"), "ecmp",
                      seed=3)
    assert np.array_equal(np.asarray(gate.finish_ticks),
                          np.asarray(static.finish_ticks))


# -------------------------------------------------------------------- drr
def test_drr_registered_and_runs(small):
    assert "drr" in SHARE_POLICIES
    topo, wl = small
    cfg = SimParams(n_ticks=2500, window=8, record_every=10,
                    share_policy="drr")
    res = simulate(topo, wl, cfg, routing="balanced", seed=0)
    cct = metrics.cct_seconds(res, wl, cfg)[0]
    ideal = metrics.ideal_cct(wl, 0, 10e9 / 8)
    # balanced single-job ring: drr == equal split == ideal lockstep
    assert np.isfinite(cct) and cct < 1.6 * ideal


def test_drr_splits_port_equally_ignoring_weights():
    """Two chain jobs share one egress port: drr serves them 50/50 even
    with unequal wfq weights (quantum is per-flow, not per-weight)."""
    topo = make_leaf_spine(4, 2, 2)
    b = WorkloadBuilder()
    b.add_chain_job(pairs=[(0, 2)], steps=1, chunk_bytes=4e6)
    b.add_chain_job(pairs=[(1, 2)], steps=1, chunk_bytes=4e6)
    wl = b.build()
    cfg = SimParams(n_ticks=8000, window=8, record_every=10,
                    share_policy="drr", red_pmax=0.0)
    res = simulate(topo, wl, cfg, routing="balanced", seed=0,
                   job_weight=np.asarray([1.0, 3.0]))
    ft = np.asarray(res.finish_ticks).astype(float)
    # both at cap/2 until the first finishes -> equal finish times
    t_half = 4e6 / (1.25e9 * 0.5) / cfg.dt
    assert ft[0] == pytest.approx(t_half, rel=0.05)
    assert ft[1] == pytest.approx(t_half, rel=0.05)


def test_drr_selectable_from_registry():
    from benchmarks.common import build_scenario
    built = build_scenario("table1_ring", share_policy="drr", passes=1)
    assert built.cfg.share_policy == "drr"


# ------------------------------------------------------- benchmark caching
def test_cached_keys_on_config(tmp_path, monkeypatch):
    import benchmarks.common as common
    monkeypatch.setattr(common, "CACHE", tmp_path / "c.json")
    calls = []

    def make(v):
        def fn():
            calls.append(v)
            return {"v": v}
        return fn

    assert common.cached("x", make(1), config={"a": 1})["v"] == 1
    # same name, different overrides -> distinct key, recomputed
    assert common.cached("x", make(2), config={"a": 2})["v"] == 2
    # repeat of the first -> served from cache, no recompute
    assert common.cached("x", make(3), config={"a": 1})["v"] == 1
    assert calls == [1, 2]


def test_cached_discards_old_schema(tmp_path, monkeypatch):
    import json

    import benchmarks.common as common
    path = tmp_path / "c.json"
    path.write_text(json.dumps({"x": {"v": "stale"}}))   # pre-split cache
    monkeypatch.setattr(common, "CACHE", path)
    out = common.cached("x", lambda: {"v": "fresh"})
    assert out["v"] == "fresh"
    data = json.loads(path.read_text())
    assert data["__schema__"] == common.CACHE_SCHEMA


@pytest.mark.parametrize("env_dir", [None, "placed-outside"])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, wins and the program sets no
    cache of its own; otherwise the cache sits at the fixed
    <repo>/.jax_cache."""
    from pathlib import Path

    import benchmarks.common as common
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(common.__file__).resolve().parents[1] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert common.enable_compile_cache() == want
        set_dir = jax.config.jax_compilation_cache_dir
        assert set_dir == (want if env_dir is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
