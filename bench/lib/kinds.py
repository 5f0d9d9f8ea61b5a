"""The two kinds of traffic a mix can name (``"kind"`` in its file).

* ``sweep``: a knob grid (the mix's ``axes`` crossed over the
  configuration's engine constants) through ``simulate_grid``, one
  dispatch of ``horizon_ticks`` ticks after another, each with lane
  seeds of its own drawn from the run's seed.  Measures lane-ticks per
  second over whole dispatches.
* ``online``: one lane through ``SimController.step()`` with windows of
  ``window_ticks`` ticks; after each window the mix's policy chooses the
  next action from the window's observations, and the controller resets
  when every job has finished.  Measures the mean wall time of a step.

Every call into the program is wrapped in a host span (``bench.dispatch``,
``bench.step``) that the trace reduction reads.  After the window the
mix hands what it checks to the plain reference: one dispatch drawn
from the seed, or a sample of steps drawn from the seed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from reference.build import deployment
from reference.engine import STATE, Engine, knob_lanes

from . import compare
from .cells import policy
from .deploy import knob_points, program_inputs, sim_params


def lane_seed(seed: int, i: int) -> int:
    """A 30-bit lane seed for unit ``i`` of a run: any whole ``--seed``
    maps to seeds that the simulator's int32 hash salt holds."""
    ss = np.random.SeedSequence([abs(int(seed)), int(seed < 0), i + 1])
    return int(ss.generate_state(1)[0] % (1 << 30))


def _span(name):
    return jax.profiler.TraceAnnotation(name)


class _References:
    """The reference engine of a configuration, one per precision."""

    def __init__(self, cfg):
        self.cfg, self.dep, self.engines = cfg, deployment(cfg), {}

    def __call__(self, dtype) -> Engine:
        name = jnp.dtype(dtype).name
        if name not in self.engines:
            self.engines[name] = Engine(self.dep, self.cfg["engine"], dtype)
        return self.engines[name]


class Sweep:

    def __init__(self, cell, seed: int):
        from repro.core.netsim import grid_from_params

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.seed = cfg, seed
        self.topo, self.wl = program_inputs(cfg)
        self.points = knob_points(cfg, tr)
        self.n_ticks = int(cfg["horizon_ticks"])
        self.struct, self.knobs = grid_from_params(
            [sim_params(p, tr, self.n_ticks) for p in self.points])
        self.devices = None if cell.chips == 1 else cell.chips
        self.results, self.walls = [], []
        self.engine = _References(cfg)

    @property
    def lanes(self) -> int:
        return len(self.points)

    def _dispatch(self, i):
        from repro.core.netsim import simulate_grid

        with _span("bench.dispatch"):
            res = simulate_grid(self.topo, self.wl, self.struct, self.knobs,
                                (lane_seed(self.seed, i),),
                                devices=self.devices)
            return jax.block_until_ready(res)

    def warm_up(self):
        self._dispatch(-1)

    def window(self, seconds: float, max_units: int | None = None):
        deadline = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < deadline and (max_units is None
                                                  or i < max_units):
            t = time.perf_counter()
            self.results.append(self._dispatch(i))
            self.walls.append(time.perf_counter() - t)
            i += 1

    def measured(self) -> dict:
        lane_ticks = self.lanes * self.n_ticks * len(self.walls)
        return {"lane_ticks_per_s": lane_ticks / sum(self.walls),
                "units": len(self.walls), "lane_ticks": lane_ticks}

    # -- the check -------------------------------------------------------
    def take_outputs(self):
        """The dispatch to check, drawn from the seed, on the host; the
        program's other results are freed."""
        rng = np.random.default_rng([abs(int(self.seed)), 7])
        self.checked = int(rng.integers(len(self.results)))
        res = jax.device_get(self.results[self.checked])
        self.results = []
        self.prog = {
            "min_wire": res.ts_min_wire[:, 0], "max_wire": res.ts_max_wire[:, 0],
            "done_min": res.ts_done_min[:, 0], "tput": res.ts_throughput[:, 0],
            "qmax": res.ts_qmax[:, 0], "alpha_max": res.ts_alpha_max[:, 0],
            "finish": res.finish_ticks[:, 0],
            "job_finish": res.job_finish_ticks[:, 0]}

    def reference(self, dtype):
        s = lane_seed(self.seed, self.checked)
        eng = self.engine(dtype)
        keys = jnp.stack([jax.random.PRNGKey(s)] * self.lanes)
        state, series = eng.run(eng.init_state(keys),
                                knob_lanes(self.points, [s] * self.lanes,
                                           dtype), 0, self.n_ticks)
        out = jax.device_get(series)
        out["finish"] = np.asarray(state["finish"])
        out["job_finish"] = np.asarray(state["job_finish"])
        return out

    def readings(self) -> dict:
        return compare.series_readings(self.prog,
                                       self.reference(jnp.float32))

    def control_readings(self, dtype) -> dict:
        return compare.series_readings(self.reference(dtype),
                                       self.reference(jnp.float32))


class Online:

    def __init__(self, cell, seed: int):
        from repro.core.netsim import SimController

        cfg, tr = cell.config, cell.traffic
        self.cfg, self.tr, self.seed = cfg, tr, seed
        topo, wl = program_inputs(cfg)
        self.point = knob_points(cfg, tr)[0]
        self.W = int(tr["window_ticks"])
        self.ctl = SimController(topo, wl, sim_params(self.point, tr, self.W),
                                 window_ticks=self.W,
                                 seed=lane_seed(seed, 0))
        self.policy = policy(tr["policy"])()
        rng = np.random.default_rng([abs(int(seed)), 11])
        self.check_at = {0} | set(rng.choice(
            np.arange(1, tr["check_from_steps"]), tr["check_steps"] - 1,
            replace=False).tolist())
        self.walls, self.kept = [], {}
        self.engine = _References(cfg)

    def warm_up(self):
        """Every program the window runs: a step with an action, the
        observation, and a reset; the window starts from tick 0."""
        _, obs = self.ctl.step(self.policy.knobs)
        float(np.sum(obs.stats.tput))
        self.ctl.reset()
        self.policy = policy(self.tr["policy"])()

    def window(self, seconds: float, max_units: int | None = None):
        action, knobs = None, dict(self.point)
        deadline = time.perf_counter() + seconds
        i = 0
        while time.perf_counter() < deadline and (max_units is None
                                                  or i < max_units):
            t = time.perf_counter()
            pre = self.ctl.state
            with _span("bench.step"):
                state, obs = self.ctl.step(action)
            if action:
                knobs.update(action)
            if i in self.check_at:
                self.kept[i] = (pre, state, obs.samples, dict(knobs))
            if obs.done:
                self.ctl.reset()
                action = None
            else:
                action = self.policy(i, float(np.sum(obs.stats.tput)))
            self.walls.append(time.perf_counter() - t)
            i += 1

    def measured(self) -> dict:
        w = np.asarray(self.walls)
        return {"step_ms": 1e3 * w.sum() / len(w), "units": len(w),
                "step_ms_p50": 1e3 * float(np.percentile(w, 50)),
                "step_ms_p95": 1e3 * float(np.percentile(w, 95)),
                "lane_ticks": len(w) * self.W}

    # -- the check -------------------------------------------------------
    def take_outputs(self):
        self.checked = {}
        for i, (pre, post, samples, knobs) in self.kept.items():
            pre, post, samples = jax.device_get((pre, post, samples))
            self.checked[i] = (pre, post, samples, knobs)
        self.kept = {}
        self.ctl.state = None

    def _ref(self, i, dtype):
        pre, _, _, knobs = self.checked[i]
        eng = self.engine(dtype)
        s0 = {k: np.asarray(getattr(pre.engine, k))[None] for k in STATE}
        for k in s0:
            if s0[k].dtype.kind == "f":
                s0[k] = s0[k].astype(dtype)
        state, series = eng.run(s0, knob_lanes([knobs], [lane_seed(
            self.seed, 0)], dtype), int(pre.tick), self.W)
        state = {k: np.asarray(v[0]) for k, v in jax.device_get(state).items()}
        return state, jax.device_get(series)

    @staticmethod
    def _prog(post, samples):
        state = {k: np.asarray(getattr(post.engine, k)) for k in STATE}
        series = {"min_wire": samples.ts_min_wire,
                  "max_wire": samples.ts_max_wire,
                  "done_min": samples.ts_done_min,
                  "tput": samples.ts_throughput, "qmax": samples.ts_qmax,
                  "alpha_max": samples.ts_alpha_max}
        return state, {k: np.asarray(v)[None] for k, v in series.items()}

    @staticmethod
    def _readings(a, b):
        (sa, xa), (sb, xb) = a, b
        r = compare.series_readings(xa, xb)
        s = compare.state_readings(sa, sb)
        r["int_mismatch"] += s["int_mismatch"]
        r["state_gap"] = s["state_gap"]
        return r

    def readings(self) -> dict:
        return compare.merge([
            self._readings(self._prog(*self.checked[i][1:3]),
                           self._ref(i, jnp.float32))
            for i in sorted(self.checked)])

    def control_readings(self, dtype) -> dict:
        return compare.merge([
            self._readings(self._ref(i, dtype), self._ref(i, jnp.float32))
            for i in sorted(self.checked)])


KINDS = {"sweep": Sweep, "online": Online}
