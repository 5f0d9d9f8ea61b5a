"""Online control plane: a ``step(state, action) -> (state, obs)`` API.

Symphony is an *online* mechanism — it reads live congestion signals and
throttles outpacing flows mid-collective — and this module gives the
repo's engine the matching interface.  A simulation is no longer a
closed one-shot scan: :class:`SimController` owns a checkpointable
:class:`~repro.core.netsim.params.SimState`, advances it one *control
window* at a time through :func:`~repro.core.netsim.simulator.run_window`
(one ``lax.scan`` chunk, compiled once, reused across windows), and lets
every window retune :class:`~repro.core.netsim.params.RuntimeKnobs`
fields via :func:`apply_action` — a pure pytree update on traced leaves,
so knob changes between windows NEVER retrace (``core_trace_count``
advances by exactly 1 across any number of steps).

Gym-flavored usage (cf. RealVNF's ``SimulatorInterface`` in PAPERS.md)::

    ctl = SimController(topo, wl, cfg, window_ticks=640, seed=3)
    state, obs = ctl.step()                      # run one window
    while not obs.done:
        action = {"tau": policy(obs), "k": 0.02}
        state, obs = ctl.step(action)            # retune mid-flight, free

``obs`` carries the per-window alpha/queue/throughput summaries from
:mod:`repro.core.netsim.metrics` plus job-completion flags; ``state`` is
the full resumable checkpoint (``jax.device_get`` it to snapshot,
:meth:`SimController.restore` to rewind — resuming is bit-for-bit
identical to never having paused).
"""
from __future__ import annotations

from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import metrics
from .params import RuntimeKnobs, SimParams, SimState, SimStructure
from .simulator import (I32MAX, Static, WindowSamples, _resolve_routing,
                        build_static, init_state, run_window, wl_arrays)
from .topology import Topology
from .workload import Workload

__all__ = ["ACTION_FIELDS", "StepObs", "SimController", "apply_action"]

# Symphony shortcuts: action keys rewriting knobs.sym.<field>.  Every
# top-level RuntimeKnobs field name (red_pmax, cc_g, sym_on, pq_on,
# sym_win_ticks, ...) is also a valid action key.
_SYM_FIELDS = ("k", "tau", "n_warmup", "n_sample", "alpha_max")
ACTION_FIELDS = tuple(f for f in RuntimeKnobs._fields if f != "sym") \
    + _SYM_FIELDS


def apply_action(knobs: RuntimeKnobs, action: Mapping[str, float]
                 ) -> RuntimeKnobs:
    """Retune knob values from an action dict — a pure pytree update.

    Keys are top-level :class:`RuntimeKnobs` fields (``"red_pmax"``,
    ``"sym_on"``, ``"sym_win_ticks"``, ...) or Symphony shortcuts
    (``"tau"``, ``"k"``, ``"alpha_max"``, ``"n_warmup"``,
    ``"n_sample"``) that rewrite ``knobs.sym``.  New values are cast to
    the existing leaf's dtype, so the updated pytree has the identical
    structure/dtypes and a jitted consumer never retraces.
    """
    sym = knobs.sym
    top: dict = {}
    sym_upd: dict = {}
    for name, val in action.items():
        if name in _SYM_FIELDS:
            sym_upd[name] = val
        elif name == "sym":
            raise ValueError(
                "set Symphony fields individually (tau/k/alpha_max/"
                "n_warmup/n_sample), not the whole 'sym' bundle")
        elif name in RuntimeKnobs._fields:
            top[name] = val
        else:
            raise ValueError(
                f"unknown action field {name!r}; have {ACTION_FIELDS}")

    def cast(old, new):
        leaf = jnp.asarray(old)
        return jnp.asarray(new, leaf.dtype)

    if sym_upd:
        sym = sym._replace(**{k: cast(getattr(sym, k), v)
                              for k, v in sym_upd.items()})
    return knobs._replace(
        sym=sym, **{k: cast(getattr(knobs, k), v) for k, v in top.items()})


class StepObs(NamedTuple):
    """What one control window observed (host-side numpy)."""
    tick: int                      # tick cursor AFTER this window
    t: float                       # same, in simulated seconds
    stats: metrics.WindowStats     # alpha/queue/throughput summaries
    samples: WindowSamples         # the window's raw sampled series
    job_finished: np.ndarray       # [J] bool
    done: bool                     # every job finished


class SimController:
    """Stateful windowed driver over ``init_state`` / ``run_window``.

    Owns the :class:`Static` arrays, the current :class:`RuntimeKnobs`,
    and the resumable :class:`SimState`; every :meth:`step` advances one
    control window and returns ``(state, obs)``.  The windowed engine
    compiles ONCE per ``(structure, window_ticks)`` and is reused across
    steps, actions, and even controller instances.
    """

    def __init__(self, topo: Topology, wl: Workload, cfg: SimParams,
                 *, window_ticks: int | None = None, routing: str = "ecmp",
                 seed: int = 0, bg_base=None, bg_amp=None, bg_period=1e-3,
                 bg_duty=0.0, job_weight=None):
        cfg, mode = _resolve_routing(cfg, routing)
        if isinstance(cfg, SimParams):
            struct, knobs = cfg.split()
        else:                         # a SimStructure: default knob values
            struct, knobs = cfg, SimParams().knobs()
        self.struct: SimStructure = struct
        self.knobs: RuntimeKnobs = knobs
        self.wl = wl
        self.st: Static = build_static(
            topo, wl, mode, seed, bg_base, bg_amp, bg_period, bg_duty,
            struct.dt, deploy=struct.deploy, job_weight=job_weight)
        self.wla = wl_arrays(wl, struct.dt)
        R = struct.record_every
        w = R if window_ticks is None else int(window_ticks)
        if w <= 0 or w % R:
            raise ValueError(
                f"window_ticks must be a positive multiple of "
                f"record_every={R}, got {window_ticks}")
        self.window_ticks = w
        self.steps = 0                # step() calls, the span's ``step`` arg
        self._seed = seed
        self.state: SimState = init_state(
            self.st, self.wla, struct, jax.random.PRNGKey(seed))

    # ------------------------------------------------------------- control
    def step(self, action: Mapping[str, float] | None = None,
             n_ticks: int | None = None) -> tuple[SimState, StepObs]:
        """Apply ``action`` (optional knob retunes), run one window.

        Profiler spans: ``netsim.step`` (args ``step``, the controller's
        step count, and ``ticks``) around ``netsim.step.action``, the
        ``netsim.window.*`` spans of :func:`run_window`, and
        ``netsim.step.observe`` (the readbacks and the summary)."""
        n = self.window_ticks if n_ticks is None else n_ticks
        with TraceAnnotation("netsim.step", step=self.steps, ticks=n):
            self.steps += 1
            if action:
                with TraceAnnotation("netsim.step.action"):
                    self.knobs = apply_action(self.knobs, action)
            self.state, samples = run_window(
                self.st, self.wla, self.struct, self.knobs, self.state, n)
            with TraceAnnotation("netsim.step.observe"):
                jf = np.asarray(self.state.engine.job_finish)
                finished = jf != I32MAX
                tick = int(self.state.tick)
                obs = StepObs(
                    tick=tick, t=tick * self.struct.dt,
                    stats=metrics.window_summary(samples), samples=samples,
                    job_finished=finished, done=bool(finished.all()))
        return self.state, obs

    def run(self, n_windows: int,
            policy=None) -> StepObs:
        """Convenience driver: ``n_windows`` steps (or until done);
        ``policy(obs) -> action|None`` is consulted after each window."""
        obs = None
        action = None
        for _ in range(n_windows):
            _, obs = self.step(action)
            if obs.done:
                break
            action = policy(obs) if policy is not None else None
        return obs

    # ---------------------------------------------------- checkpoint/resume
    def checkpoint(self) -> SimState:
        """A host-side snapshot of the current state (device_get'd, so it
        survives donation/aliasing on the pallas window path)."""
        return jax.device_get(self.state)

    def restore(self, state: SimState) -> None:
        """Rewind/teleport to a checkpointed state."""
        self.state = jax.tree.map(jnp.asarray, state)

    def reset(self, seed: int | None = None) -> SimState:
        """Back to tick 0 (optionally reseeding the CC coin flips)."""
        if seed is not None:
            self._seed = seed
        self.state = init_state(
            self.st, self.wla, self.struct, jax.random.PRNGKey(self._seed))
        return self.state
