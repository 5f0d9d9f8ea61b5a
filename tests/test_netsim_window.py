"""Tests for the online control plane (PR 10).

* Windowed checkpoint/resume: `init_state` + `run_window` replayed over
  any window split is bit-for-bit identical to the one-shot `simulate`
  on the Table-1 goldens — integer outputs and `ts_alpha_max` — for the
  XLA staged path AND the fused pallas path with `tick_window`/`blk`
  tiling active.
* The `step(state, action)` API: knob retunes between windows never
  retrace (`core_trace_count` advances by exactly 1), stepping with
  unchanged knobs matches the one-shot run, and checkpoint/restore
  rewinds deterministically.
* Dependency-triggered arrivals: `set_trigger` releases a job only when
  its dependency completes (plus delay), `add_poisson_churn` is
  reproducible, and triggered workloads run unchanged under the grid
  executor.
"""
import jax
import numpy as np
import pytest

from repro.core.netsim import (SimController, SimParams, WorkloadBuilder,
                               apply_action, build_static, core_trace_count,
                               init_state, make_leaf_spine, run_window,
                               simulate, simulate_grid)
from repro.core.netsim.simulator import I32MAX, _resolve_routing, wl_arrays

from netsim_goldens import GOLDEN_JOB


def _table1():
    topo = make_leaf_spine(32, 4, 4)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(32)), ring_size=8, chunk_bytes=1e6,
                   passes=2, barrier=False)
    return topo, b.build()


def _small():
    topo = make_leaf_spine(8, 2, 2)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                   passes=1, barrier=False)
    return topo, b.build()


def _prep(topo, wl, cfg, routing="ecmp", seed=0):
    """The same static/knob split `simulate` performs internally."""
    cfg, mode = _resolve_routing(cfg, routing)
    st = build_static(topo, wl, mode, seed, dt=cfg.dt, deploy=cfg.deploy)
    struct, knobs = cfg.split()
    return st, wl_arrays(wl, cfg.dt), struct, knobs


def _run_split(st, wla, struct, knobs, seed, splits):
    """Resume across `splits` windows; returns (state, concatenated samples)."""
    state = init_state(st, wla, struct, seed)
    chunks = []
    for n in splits:
        state, samples = run_window(st, wla, struct, knobs, state, n)
        chunks.append(samples)
    cat = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *chunks)
    return state, cat


def _assert_resume_equal(one, state, samples, n_ticks):
    assert int(state.tick) == n_ticks
    assert np.array_equal(np.asarray(state.engine.finish),
                          np.asarray(one.finish_ticks))
    assert np.array_equal(np.asarray(state.engine.job_finish),
                          np.asarray(one.job_finish_ticks))
    assert np.array_equal(np.asarray(samples.ts_alpha_max),
                          np.asarray(one.ts_alpha_max))
    assert np.array_equal(np.asarray(samples.ts_done_min),
                          np.asarray(one.ts_done_min))


# ------------------------------------------------- resume equivalence (golden)
def test_resume_equivalence_table1_goldens():
    """Uneven window splits of the 20k-tick Table-1 run reproduce the
    one-shot goldens bit-for-bit (ecmp base + sym).  The integer outputs
    are pinned by the golden constants; the sym variant additionally
    checks the sampled series bitwise against a one-shot run."""
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64)
    splits = (6_400, 6_400, 6_400, 800)         # uneven, sums to 20_000

    st, wla, struct, knobs = _prep(topo, wl, cfg, seed=3)
    state, _ = _run_split(st, wla, struct, knobs, 3, splits)
    assert int(state.engine.job_finish[0]) == GOLDEN_JOB["ecmp_base"]

    sym = cfg._replace(sym_on=True)
    one = simulate(topo, wl, sym, routing="ecmp", seed=3)
    st, wla, struct, knobs = _prep(topo, wl, sym, seed=3)
    state, samples = _run_split(st, wla, struct, knobs, 3, splits)
    assert int(state.engine.job_finish[0]) == GOLDEN_JOB["ecmp_sym"]
    _assert_resume_equal(one, state, samples, cfg.n_ticks)
    # float series concatenate exactly too (same compiled tick program)
    assert np.array_equal(np.asarray(samples.ts_throughput),
                          np.asarray(one.ts_throughput))


@pytest.mark.slow
def test_resume_equivalence_balanced_and_pq():
    topo, wl = _table1()
    cfg = SimParams(n_ticks=20_000, window=64)
    splits = (2_600, 400, 17_000)
    for name, c, routing in (
            ("balanced_sym", cfg._replace(sym_on=True), "balanced"),
            ("ecmp_pq", cfg._replace(pq_on=True), "ecmp")):
        one = simulate(topo, wl, c, routing=routing, seed=3)
        st, wla, struct, knobs = _prep(topo, wl, c, routing=routing, seed=3)
        state, samples = _run_split(st, wla, struct, knobs, 3, splits)
        assert int(state.engine.job_finish[0]) == GOLDEN_JOB[name]
        _assert_resume_equal(one, state, samples, cfg.n_ticks)


def test_resume_equivalence_pallas_tiled():
    """Windowed resume composes with the fused pallas backend with
    multi-tick windows (tick_window=5) and lane tiling (blk=16 < FW=64)
    active — still bit-for-bit vs the one-shot run."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=600, window=8, record_every=10, sym_on=True,
                    backend="pallas", segsum="onehot", tick_window=5, blk=16)
    one = simulate(topo, wl, cfg, routing="ecmp", seed=0)
    st, wla, struct, knobs = _prep(topo, wl, cfg, seed=0)
    state, samples = _run_split(st, wla, struct, knobs, 0,
                                (100, 100, 400))
    _assert_resume_equal(one, state, samples, cfg.n_ticks)


def test_resume_arbitrary_split_matches_oneshot():
    """Window boundaries anywhere on the record grid — including a
    single-record-period window — replay identically."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=400, window=8, record_every=10, sym_on=True)
    one = simulate(topo, wl, cfg, routing="ecmp", seed=1)
    st, wla, struct, knobs = _prep(topo, wl, cfg, seed=1)
    state, samples = _run_split(st, wla, struct, knobs, 1,
                                (10, 30, 200, 150, 10))
    _assert_resume_equal(one, state, samples, cfg.n_ticks)


def test_run_window_validates_tick_grid():
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, record_every=10)
    st, wla, struct, knobs = _prep(topo, wl, cfg)
    state = init_state(st, wla, struct, 0)
    for bad in (0, -10, 15):
        with pytest.raises(ValueError, match="record_every"):
            run_window(st, wla, struct, knobs, state, bad)


# --------------------------------------------------------- step(state, action)
def test_step_one_compile_across_knob_changes():
    """Retuning knobs between windows NEVER retraces the engine: the
    acceptance contract is ONE compile across repeated step() calls with
    different knob values (including Symphony shortcut fields)."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=400, window=8, record_every=10, sym_on=True)
    ctl = SimController(topo, wl, cfg, window_ticks=50, seed=0)
    c0 = core_trace_count()
    for action in (None, {"tau": 0.1}, {"k": 0.02, "tau": 0.3},
                   {"red_pmax": 0.5}, {"alpha_max": 4.0},
                   {"sym_on": False}, {"sym_on": True, "tau": 0.05}):
        ctl.step(action)
    assert core_trace_count() - c0 == 1


def test_step_resume_matches_oneshot():
    """Stepping with unchanged knobs IS the one-shot run, bit-for-bit;
    obs carries the per-window summaries."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=400, window=8, record_every=10, sym_on=True)
    one = simulate(topo, wl, cfg, routing="ecmp", seed=0)
    ctl = SimController(topo, wl, cfg, window_ticks=80, seed=0)
    chunks = []
    for _ in range(5):
        state, obs = ctl.step()
        chunks.append(obs.samples)
    assert obs.tick == 400 and obs.t == pytest.approx(400 * cfg.dt)
    cat = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *chunks)
    _assert_resume_equal(one, state, cat, cfg.n_ticks)
    # obs flags agree with the engine's completion state
    jf = np.asarray(one.job_finish_ticks)
    assert np.array_equal(obs.job_finished, jf != I32MAX)
    assert obs.done == bool((jf != I32MAX).all())
    assert obs.stats.alpha_max == pytest.approx(
        float(np.asarray(chunks[-1].ts_alpha_max).max()))
    assert obs.stats.tput.shape == (wl.n_jobs,)


def test_checkpoint_restore_rewind():
    """restore() rewinds to a snapshot and replays identically."""
    topo, wl = _small()
    cfg = SimParams(n_ticks=400, window=8, record_every=10, sym_on=True)
    ctl = SimController(topo, wl, cfg, window_ticks=100, seed=0)
    ctl.step()
    snap = ctl.checkpoint()                     # host-side copy at tick 100
    sa, _ = ctl.step()
    ctl.restore(snap)
    sb, _ = ctl.step()
    assert int(sa.tick) == int(sb.tick) == 200
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_controller_window_validation():
    topo, wl = _small()
    cfg = SimParams(n_ticks=100, window=8, record_every=10)
    with pytest.raises(ValueError, match="record_every"):
        SimController(topo, wl, cfg, window_ticks=15)


def test_apply_action_preserves_structure():
    """Actions update values without touching pytree structure or leaf
    dtypes (what makes knob retunes trace-free)."""
    knobs = SimParams().knobs()
    new = apply_action(knobs, {"tau": 0.25, "red_pmax": 0.9, "sym_on": True,
                               "k": 0.01})
    assert jax.tree.structure(new) == jax.tree.structure(knobs)
    for a, b in zip(jax.tree.leaves(knobs), jax.tree.leaves(new)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.asarray(a).shape == np.asarray(b).shape
    assert float(new.sym.tau) == pytest.approx(0.25)
    assert float(new.sym.k) == pytest.approx(0.01)
    assert float(new.red_pmax) == pytest.approx(0.9)
    assert float(new.sym_on) == 1.0
    # untouched fields keep their values
    assert float(new.sym.alpha_max) == float(knobs.sym.alpha_max)
    with pytest.raises(ValueError, match="unknown action field"):
        apply_action(knobs, {"bogus": 1.0})
    with pytest.raises(ValueError, match="individually"):
        apply_action(knobs, {"sym": None})


# ------------------------------------------------ dependency-triggered arrivals
def _two_job_wl(trigger=None, collectives=None, delay=0.0):
    b = WorkloadBuilder()
    # barrier=True keeps job 0's passes as separate segments, so a
    # collectives=1 trigger can fire mid-job
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                   passes=2, barrier=True)
    b.add_ring_job(hosts=list(range(8, 16)), ring_size=4, chunk_bytes=1e6,
                   passes=1, barrier=False)
    if trigger:
        b.set_trigger(1, after_job=0, collectives=collectives, delay=delay)
    return b.build()


def test_trigger_releases_after_dependency():
    topo = make_leaf_spine(16, 2, 2)
    cfg = SimParams(n_ticks=1_600, window=8, record_every=10)

    free = simulate(topo, wl := _two_job_wl(), cfg, routing="ecmp", seed=0)
    jf_free = np.asarray(free.job_finish_ticks)
    trig = simulate(topo, _two_job_wl(trigger=True), cfg, routing="ecmp",
                    seed=0)
    jf = np.asarray(trig.job_finish_ticks)
    # untriggered: both jobs start at t=0, job 1 (1 pass) finishes first;
    # triggered: job 1 is held until job 0 completes every collective.
    assert jf_free[1] < jf_free[0]
    assert jf[1] > jf[0]
    assert jf[1] > jf_free[1]

    # a pure delay shifts the released job exactly (job 0 is done by then,
    # so job 1 replays contention-free at the offset); an immediate (d=0)
    # release is evaluated at the end of the trigger tick, so the shift
    # relative to it is d - 1
    d = 50
    trig_d = simulate(topo, _two_job_wl(trigger=True, delay=d * cfg.dt),
                      cfg, routing="ecmp", seed=0)
    assert int(trig_d.job_finish_ticks[1]) == int(jf[1]) + d - 1

    # triggering on the FIRST collective of the 2-pass job releases earlier
    trig_c1 = simulate(topo, _two_job_wl(trigger=True, collectives=1),
                       cfg, routing="ecmp", seed=0)
    assert int(trig_c1.job_finish_ticks[1]) < int(jf[1])


def test_trigger_resume_and_grid_consistent():
    """Triggers evaluate inside the traced tick, so they compose with
    windowed resume and the one-compile grid executor bit-for-bit."""
    topo = make_leaf_spine(16, 2, 2)
    wl = _two_job_wl(trigger=True, delay=1e-4)
    cfg = SimParams(n_ticks=1_000, window=8, record_every=10, sym_on=True)
    one = simulate(topo, wl, cfg, routing="ecmp", seed=0)
    # windowed resume
    st, wla, struct, knobs = _prep(topo, wl, cfg, seed=0)
    state, samples = _run_split(st, wla, struct, knobs, 0, (300, 100, 600))
    _assert_resume_equal(one, state, samples, cfg.n_ticks)
    # 1-point grid slice
    gres = simulate_grid(topo, wl, struct,
                         jax.tree.map(lambda x: x[None], knobs),
                         seeds=(0,), routing="ecmp")
    assert np.array_equal(np.asarray(gres.job_finish_ticks)[0, 0],
                          np.asarray(one.job_finish_ticks))
    assert np.array_equal(np.asarray(gres.finish_ticks)[0, 0],
                          np.asarray(one.finish_ticks))


def test_trigger_validation():
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(4)), ring_size=4, chunk_bytes=1e6,
                   passes=2, barrier=False)
    b.add_ring_job(hosts=list(range(4, 8)), ring_size=4, chunk_bytes=1e6,
                   passes=1, barrier=False)
    with pytest.raises(ValueError, match="itself"):
        b.set_trigger(0, after_job=0)
    with pytest.raises(ValueError, match="unknown job"):
        b.set_trigger(1, after_job=5)
    with pytest.raises(ValueError, match="collectives"):
        b.set_trigger(1, after_job=0, collectives=0)
    with pytest.raises(ValueError, match="delay"):
        b.set_trigger(1, after_job=0, delay=-1.0)
    # asking for more collectives than the dependency runs fails at build()
    b.set_trigger(1, after_job=0, collectives=3)
    with pytest.raises(ValueError, match="only runs"):
        b.build()


def test_poisson_churn_builder():
    def mk(seed):
        b = WorkloadBuilder()
        b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                       passes=1, barrier=False)
        jobs = b.add_poisson_churn(
            [list(range(8, 12)), list(range(12, 16))],
            rate_hz=500.0, horizon_s=0.1, ring_size=4, chunk_bytes=1e5,
            passes=1, seed=seed, max_jobs=3)
        return jobs, b.build()

    jobs, wl = mk(7)
    assert len(jobs) == 3                        # max_jobs honored
    starts = np.asarray(wl.start_time)[jobs]
    assert np.all(np.diff(starts) > 0)           # Poisson arrivals ordered
    assert np.all(starts > 0) and np.all(starts < 0.1)
    assert np.all(np.asarray(wl.trig_job)[jobs] == -1)   # churn = fixed starts
    # reproducible for a seed, different across seeds
    _, wl2 = mk(7)
    assert np.array_equal(np.asarray(wl2.start_time), np.asarray(wl.start_time))
    _, wl3 = mk(8)
    assert not np.array_equal(np.asarray(wl3.start_time)[1:],
                              np.asarray(wl.start_time)[1:])
    with pytest.raises(ValueError, match="rate_hz"):
        WorkloadBuilder().add_poisson_churn([[0, 1]], rate_hz=0.0,
                                            horizon_s=1.0)
    with pytest.raises(ValueError, match="empty host_groups"):
        WorkloadBuilder().add_poisson_churn([], rate_hz=1.0, horizon_s=1.0)
