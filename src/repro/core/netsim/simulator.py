"""Fluid-flow network simulator for ring-style collectives (our
Astra-Sim + NS-3), built as a staged engine over a generic link table.

One `lax.scan` over fixed ticks of `dt` seconds.  All state is arrays, so
the whole simulation jits and vmaps over seeds/parameters.  The per-tick
body is not monolithic: it is composed from the individually-testable stage
functions in :mod:`repro.core.netsim.stages` (start gating, route selection,
bandwidth sharing, queues/RED, Symphony marking, DCQCN rate control,
segment/job progress, metrics) — `simulate_core` only assembles them into
the scan and handles recording.

Configuration is split along the jit boundary (:mod:`.params`):

* :class:`SimStructure` — static shapes / compile-time choices (`n_ticks`,
  `window`, `record_every`, `share_policy`, `deploy`, `per_step_ecmp`,
  `dt`, `mtu`, and the tick `backend`: `"xla"` staged ops vs `"pallas"`
  fused kernel, see :mod:`repro.kernels.netsim_tick`).  A jit static
  argument; changing a field recompiles.
* :class:`RuntimeKnobs` — every numeric knob (RED, DCQCN, Symphony, the
  `sym_on` / `pq_on` 0/1 gates) as traced f32/i32 leaves.  Changing values
  never recompiles, and grids of knobs vmap through ONE compilation.
* :class:`SimParams` — the backwards-compatible flat facade; `simulate`,
  `simulate_seeds` and `simulate_core` still accept it and split it
  internally, so existing callers keep working unchanged.

Entry points
------------
* :func:`simulate`        — one (params, seed) point.
* :func:`simulate_seeds`  — vmap over seeds (path draws + CC coin flips).
* :func:`simulate_grid`   — the batched grid executor: one compile,
  vmap over knob points x seeds, chunked along the knob axis to bound
  memory.  Result arrays gain leading ``[K, S]`` axes.

Multi-device dispatch
---------------------
``simulate_grid(..., devices=..., mesh=...)`` shards the flattened
``K*S`` lane axis across a 1-D device mesh via ``shard_map`` (through
:func:`repro.compat.shard_map`): every device runs
``lanes/D`` independent simulations of the SAME compiled program, so the
one-compile contract (``core_trace_count``) is unchanged.  Lane counts
that don't divide the device count are padded by repeating the last lane
and the padding is masked off the result.  ``devices="auto"`` uses all
local devices; ``chunk_knobs`` bounds the knob points resident *per
device*, so the memory bound composes with sharding.

Entities
--------
flow slot   f in [0, F): persistent (ring, member) sender->successor relation
instance    (f, w): one in-flight step-send of slot f. Steps pipeline (a node
            may start step s once it *received* s-1), so several instances of
            a slot can be concurrently active — this is the step-overlap
            phenomenon the paper studies (Fig. 1e). W = cfg.window slots,
            keyed by s % W.
link        rows of the Topology table + one trailing "null" link with
            infinite capacity (padding for short routes).

Generality
----------
* Topology is any :class:`~repro.core.netsim.topology.Topology` (2-tier
  leaf-spine, 3-tier multi-pod fat-tree, ...): routes are variable-hop
  ``[F, H]`` rows; per-step ECMP re-hashes over the per-flow candidate-path
  table ``[F, P, H]`` instead of assuming one switch tier.
* Bandwidth sharing is pluggable (``share_policy``): ``proportional``
  (default), ``pq`` strict 2-class priority, ``wfq`` weighted-fair across
  jobs (weights via ``build_static(job_weight=...)``), or ``drr`` deficit
  round-robin; the traced ``pq_on`` gate overrides to strict priority at
  runtime.
* Symphony's deployment tier is configurable (``deploy``): ``"tor"``
  (ToR-only, the paper's §5 default), ``"all"`` (every switch),
  ``"spine"`` (spine/core only).

Time is kept in integer ticks (i32) so float32 never loses precision.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import compat

from .params import (RuntimeKnobs, SimParams, SimState, SimStructure,
                     grid_from_params, merge_params, stack_knobs)
from .stages import (BIG, I32MAX, WIRE_SEG, EngineState, WLArrays,  # noqa: F401
                     BACKENDS, SHARE_POLICIES, engine_tick,
                     init_state as engine_init_state,
                     make_ctx, resolve_backend, resolve_share_policy)
from .topology import LEVEL_SPINE, LEVEL_TOR, Topology
from .workload import (Workload, balanced_choice, ecmp_choice, path_table_for,
                       routes_for)

__all__ = [
    "SimParams", "SimStructure", "RuntimeKnobs", "SimResult", "SimState",
    "Static", "WindowSamples",
    "simulate", "simulate_seeds", "simulate_grid", "simulate_core",
    "init_state", "run_window",
    "build_static", "link_domains", "grid_from_params", "stack_knobs",
    "core_trace_count", "resolve_grid_mesh", "GRID_AXIS",
]

# name of the lane axis on the 1-D grid-dispatch mesh
GRID_AXIS = "lanes"


class SimResult(NamedTuple):
    finish_ticks: jax.Array        # [F] completion tick per flow slot (I32MAX if not)
    job_finish_ticks: jax.Array    # [J]
    # sampled series, every record_every ticks:
    ts_min_wire: jax.Array         # [T, J] oldest active wire step (BIG if none)
    ts_max_wire: jax.Array         # [T, J] newest active wire step (-1 if none)
    ts_done_min: jax.Array         # [T, J] min completed local steps over flows
    ts_throughput: jax.Array       # [T, J] delivered bytes/s summed over job
    ts_qmax: jax.Array             # [T]    max queue depth (bytes)
    ts_alpha_max: jax.Array        # [T]    max Symphony alpha over ports
    # batched entry points prepend leading axes: [S, ...] for
    # simulate_seeds, [K, S, ...] for simulate_grid.


class WindowSamples(NamedTuple):
    """The sampled series of one :func:`run_window` call: the same six
    ``ts_*`` series as :class:`SimResult`, but covering only that window's
    ``n_ticks // record_every`` record periods.  Concatenating the windows
    of a split run reproduces the one-shot series exactly."""
    ts_min_wire: jax.Array         # [T, J]
    ts_max_wire: jax.Array         # [T, J]
    ts_done_min: jax.Array         # [T, J]
    ts_throughput: jax.Array       # [T, J]
    ts_qmax: jax.Array             # [T]
    ts_alpha_max: jax.Array        # [T]


class Static(NamedTuple):
    """Per-run device arrays (vmap over leading axis for multi-seed)."""
    routes: jax.Array        # [F, H] static per-flow paths (null-link padded)
    path_table: jax.Array    # [F, P, H] ECMP candidate paths per flow
    n_paths: jax.Array       # [F] candidate fan-out (hash applied modulo)
    cap: jax.Array           # [L+1] bytes/s
    link_dom: jax.Array      # [L+1] Symphony domain (switch) id; D = no Symphony
    dom_pad: jax.Array       # [D+1] zeros; carries the static domain count
    bg_base: jax.Array       # [L+1] bytes/s constant background load
    bg_amp: jax.Array        # [L+1] square-wave background amplitude
    bg_period_ticks: jax.Array  # i32 scalar
    bg_duty: jax.Array          # f32 scalar in [0,1]
    job_weight: jax.Array    # [J] weighted-fair share weights (wfq policy)
    seed: jax.Array          # i32 hash salt


def link_domains(topo: Topology, deploy: str = "tor"
                 ) -> tuple[np.ndarray, int]:
    """Map each link to its Symphony domain (the switch owning its egress
    port), honoring the deployment tier:

    * ``"tor"``   — ToR/edge switches only (paper §5 "Practical deployment")
    * ``"all"``   — every switch tier
    * ``"spine"`` — spine/aggregation and core switches only

    Returns ``(dom [L+1], D)`` where links of non-deployed switches (and
    host NICs, and the null link) map to the null domain ``D``.
    """
    lv = topo.switch_level
    if deploy == "tor":
        sel = lv == LEVEL_TOR
    elif deploy == "all":
        sel = lv >= LEVEL_TOR
    elif deploy == "spine":
        sel = lv >= LEVEL_SPINE
    else:
        raise ValueError(f"unknown deploy tier {deploy!r}")
    sw_ids = np.nonzero(sel)[0]
    D = int(sw_ids.shape[0])
    compact = np.full(topo.n_switches, -1, np.int32)
    compact[sw_ids] = np.arange(D, dtype=np.int32)
    dom = np.full(topo.n_links + 1, D, np.int32)
    owned = topo.link_switch >= 0
    mapped = compact[topo.link_switch[owned]]
    dom[:topo.n_links][owned] = np.where(mapped >= 0, mapped, D)
    return dom, D


def build_static(topo: Topology, wl: Workload, routing: str, seed: int,
                 bg_base: np.ndarray | None = None,
                 bg_amp: np.ndarray | None = None,
                 bg_period: float = 1e-3, bg_duty: float = 0.0,
                 dt: float = 10e-6, deploy: str = "tor",
                 job_weight: np.ndarray | None = None) -> Static:
    if routing == "ecmp":
        choice = ecmp_choice(topo, wl, seed)
    elif routing == "balanced":
        choice = balanced_choice(topo, wl)
    else:
        raise ValueError(routing)
    routes = routes_for(topo, wl, choice)
    paths, n_paths = path_table_for(topo, wl)
    dom, D = link_domains(topo, deploy)
    zb = np.zeros(topo.n_links + 1)
    return Static(
        routes=jnp.asarray(routes, jnp.int32),
        path_table=jnp.asarray(paths, jnp.int32),
        n_paths=jnp.asarray(n_paths, jnp.int32),
        cap=jnp.asarray(np.concatenate([topo.link_cap, [1e30]]), jnp.float32),
        link_dom=jnp.asarray(dom),
        dom_pad=jnp.zeros(D + 1, jnp.float32),
        bg_base=jnp.asarray(zb if bg_base is None else np.append(bg_base, 0.0),
                            jnp.float32),
        bg_amp=jnp.asarray(zb if bg_amp is None else np.append(bg_amp, 0.0),
                           jnp.float32),
        bg_period_ticks=jnp.asarray(max(1, round(bg_period / dt)), jnp.int32),
        bg_duty=jnp.asarray(bg_duty, jnp.float32),
        job_weight=jnp.asarray(
            np.ones(wl.n_jobs) if job_weight is None else job_weight,
            jnp.float32),
        seed=jnp.asarray(seed, jnp.int32),
    )


def wl_arrays(wl: Workload, dt: float) -> WLArrays:
    return WLArrays(
        src=jnp.asarray(wl.src), dst=jnp.asarray(wl.dst),
        pred=jnp.asarray(wl.pred), job=jnp.asarray(wl.job),
        phase=jnp.asarray(wl.phase), sps=jnp.asarray(wl.steps_per_seg),
        pass_steps=jnp.asarray(wl.pass_steps),
        total_steps=jnp.asarray(wl.total_steps()),
        n_phases=jnp.asarray(wl.n_phases),
        n_segs=jnp.asarray(wl.n_passes * wl.n_phases),
        chunk_sched=jnp.asarray(wl.chunk_sched, jnp.float32),
        gap_ticks=jnp.asarray(np.round(wl.compute_gap / dt), jnp.int32),
        start_ticks=jnp.asarray(np.round(wl.start_time / dt), jnp.int32),
        step_offset=jnp.asarray(wl.step_offset),
        fstart_ticks=jnp.asarray(np.round(wl.flow_start / dt), jnp.int32),
        trig_job=jnp.asarray(wl.trig_job, jnp.int32),
        trig_seg=jnp.asarray(wl.trig_seg, jnp.int32),
        trig_delay_ticks=jnp.asarray(np.round(wl.trig_delay / dt), jnp.int32),
    )


# ------------------------------------------------------------------- core
_TRACES = {"core": 0}


def core_trace_count() -> int:
    """How many times the engine body has been traced (== compiled) in
    this process.  The grid executor's contract — and the regression test
    / `netsim_perf` check — is that an entire knob grid adds exactly 1."""
    return _TRACES["core"]


def _window_body(ctx, cfg, sim: SimState, n_ticks: int):
    """Advance the engine ``n_ticks`` ticks from ``sim``, sampling every
    ``record_every`` ticks.  This is the ONE windowed engine body: the
    closed-form `_core_impl` runs it once from tick 0 for the whole
    horizon, and `run_window` re-enters it from any checkpointed
    :class:`~repro.core.netsim.params.SimState` — both through the same
    record-period scan, so a split run replays the identical per-tick
    program (tick indices are re-based on the traced ``sim.tick`` cursor,
    which only ever feeds integer gates, never float operands).

    Executed once per trace, so it doubles as the compile counter."""
    _TRACES["core"] += 1

    def tick_fn(state, tick):
        return engine_tick(ctx, cfg, state, tick)

    R = cfg.record_every
    n_rec = n_ticks // R
    tick0 = sim.tick

    w = int(getattr(cfg, "tick_window", 1) or 1)
    if w < 1:
        raise ValueError(f"tick_window must be >= 1, got {w}")
    if w > 1 and resolve_backend(cfg) != "pallas":
        raise ValueError(
            f"tick_window={w} > 1 requires the fused pallas backend "
            f"(got backend={cfg.backend!r}, share_policy="
            f"{cfg.share_policy!r}; wfq/drr fall back to the staged XLA "
            "path, which has no multi-tick window kernel)")
    # A window never spans a record boundary: the sample contract is "the
    # last tick of each record period", so windows chunk each period into
    # R // w full windows plus one R % w remainder window.
    w = min(w, R)

    if w > 1:
        # The window kernel donates the carried engine state: each pallas
        # call aliases its N_STATE state inputs to the state outputs
        # (window.py input_output_aliases), so this record-period scan
        # updates the state buffers in place — no extra state copy per
        # window on the pallas path.
        from ...kernels.netsim_tick.ops import engine_window_fused
        n_full, rem = divmod(R, w)

        def rec_body(state, r):
            base = tick0 + r * R
            sample = None
            if n_full:
                def win(state, j):
                    return engine_window_fused(ctx, cfg, state,
                                               base + j * w, w)
                state, samples = jax.lax.scan(win, state,
                                              jnp.arange(n_full))
                sample = jax.tree.map(lambda x: x[-1], samples)
            if rem:
                state, sample = engine_window_fused(ctx, cfg, state,
                                                    base + n_full * w, rem)
            return state, sample
    else:
        def rec_body(state, r):
            ticks = tick0 + r * R + jnp.arange(R)
            state, samples = jax.lax.scan(tick_fn, state, ticks)
            return state, jax.tree.map(lambda x: x[-1], samples)

    state, samples = jax.lax.scan(rec_body, sim.engine, jnp.arange(n_rec))
    sim = SimState(tick=tick0 + jnp.int32(n_rec * R), engine=state)
    return sim, samples


def _core_impl(st: Static, wl: WLArrays, struct: SimStructure,
               knobs: RuntimeKnobs, key: jax.Array) -> SimResult:
    """The closed-form engine body: init + one full-horizon window.
    Shared by the single-run and grid jit wrappers."""
    cfg = merge_params(struct, knobs)
    resolve_share_policy(cfg)        # fail fast on unknown policy names
    ctx = make_ctx(st, wl, cfg.window)
    sim0 = SimState(tick=jnp.int32(0), engine=engine_init_state(ctx, key))
    sim, samples = _window_body(ctx, cfg, sim0, cfg.n_ticks)
    min_w, max_w, done_min, tput, qmax, alph = samples
    return SimResult(
        finish_ticks=sim.engine.finish,
        job_finish_ticks=sim.engine.job_finish,
        ts_min_wire=min_w, ts_max_wire=max_w, ts_done_min=done_min,
        ts_throughput=tput, ts_qmax=qmax, ts_alpha_max=alph,
    )


def _flatten_lanes(st_stack: Static, knobs_stack: RuntimeKnobs,
                   keys: jax.Array):
    """Flatten the (K knobs, S seeds) cross product to a SINGLE batch axis
    of ``K*S`` lanes (lane ``i = k*S + s``, row-major) rather than nested
    vmaps: one-level batching keeps XLA's scatter-add accumulation order
    per lane identical to the unbatched program, so grid slices are
    bitwise-equal to per-point ``simulate`` calls (nested vmaps reorder
    the adds by ~1 ulp)."""
    K = int(jax.tree.leaves(knobs_stack)[0].shape[0])
    S = int(keys.shape[0])
    sts = jax.tree.map(
        lambda x: jnp.broadcast_to(
            x[None], (K,) + x.shape).reshape((K * S,) + x.shape[1:]),
        st_stack)
    kns = jax.tree.map(lambda x: jnp.repeat(x, S, axis=0), knobs_stack)
    kys = jnp.broadcast_to(keys[None], (K,) + keys.shape).reshape(
        (K * S,) + keys.shape[1:])
    return sts, kns, kys


def _lanes_impl(sts: Static, wl: WLArrays, struct: SimStructure,
                kns: RuntimeKnobs, kys: jax.Array) -> SimResult:
    """vmap the engine body over a flat lane axis (the shared inner core
    of the single-device and sharded grid programs)."""
    return jax.vmap(lambda st, kn, k: _core_impl(st, wl, struct, kn, k))(
        sts, kns, kys)


def _grid_impl(st_stack: Static, wl: WLArrays, struct: SimStructure,
               knobs_stack: RuntimeKnobs, keys: jax.Array) -> SimResult:
    """Single-device grid program: vmap knob points x seeds through one
    trace of the engine body; outputs reshaped back to leading ``[K, S]``.
    """
    K = int(jax.tree.leaves(knobs_stack)[0].shape[0])
    S = int(keys.shape[0])
    sts, kns, kys = _flatten_lanes(st_stack, knobs_stack, keys)
    flat = _lanes_impl(sts, wl, struct, kns, kys)
    return jax.tree.map(
        lambda x: x.reshape((K, S) + x.shape[1:]), flat)


_grid_core = functools.partial(jax.jit, static_argnames=("struct",))(
    _grid_impl)


def _sharded_grid_impl(st_stack: Static, wl: WLArrays,
                       knobs_stack: RuntimeKnobs, keys: jax.Array, *,
                       struct: SimStructure, mesh) -> SimResult:
    """Sharded grid program: split the flattened ``K*S`` lane axis across
    the 1-D device mesh via ``shard_map``.

    Lanes are independent simulations, so the body needs no collectives —
    each device vmaps the SAME engine trace over its ``lanes/D`` slice
    (``core_trace_count`` still advances by exactly 1 per grid).  When
    ``K*S`` does not divide the device count D, the lane axis is padded
    by repeating the last lane ("edge" padding keeps the padded work
    identical to real work, no NaN/denormal hazards) and the padding is
    masked off the output before the ``[K, S]`` reshape.
    """
    K = int(jax.tree.leaves(knobs_stack)[0].shape[0])
    S = int(keys.shape[0])
    sts, kns, kys = _flatten_lanes(st_stack, knobs_stack, keys)
    D = int(mesh.devices.size)
    pad = (-(K * S)) % D

    def edge_pad(x):
        return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), mode="edge")

    if pad:
        sts, kns, kys = jax.tree.map(edge_pad, (sts, kns, kys))
    axis = mesh.axis_names[0]
    lane = jax.sharding.PartitionSpec(axis)
    rep = jax.sharding.PartitionSpec()
    fn = compat.shard_map(
        lambda a, b, c, d: _lanes_impl(a, b, struct, c, d),
        mesh=mesh, in_specs=(lane, rep, lane, lane), out_specs=lane)
    flat = fn(sts, wl, kns, kys)
    return jax.tree.map(
        lambda x: x[:K * S].reshape((K, S) + x.shape[1:]), flat)


_sharded_core = functools.partial(jax.jit, static_argnames=("struct", "mesh"))(
    _sharded_grid_impl)


def resolve_grid_mesh(devices=None, mesh=None):
    """Resolve ``simulate_grid``'s ``devices=`` / ``mesh=`` knobs into a
    1-D lane mesh, or ``None`` for plain single-device dispatch.

    * ``mesh=Mesh``        — use as-is (must be 1-D);
    * ``devices=None``     — single device (the bitwise-stable default);
    * ``devices="auto"``   — all local devices;
    * ``devices=int``      — the first N local devices;
    * ``devices=sequence`` — exactly those ``jax.Device`` objects.

    A resolved mesh of one device is normalized to ``None``: single-lane
    meshes add dispatch overhead without buying parallelism, and the
    unsharded program is the bit-for-bit reference.
    """
    if mesh is not None:
        if devices is not None:
            raise ValueError("pass either devices= or mesh=, not both")
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"grid mesh must be 1-D, got axes {mesh.axis_names}")
        return None if mesh.devices.size == 1 else mesh
    if devices is None:
        return None
    if isinstance(devices, str):
        if devices != "auto":
            raise ValueError(f"devices= accepts 'auto', an int, or a "
                             f"device sequence; got {devices!r}")
        devs = jax.local_devices()
    elif isinstance(devices, int):
        devs = jax.local_devices()
        if not 1 <= devices <= len(devs):
            raise ValueError(
                f"devices={devices} out of range; have {len(devs)} "
                "local devices")
        devs = devs[:devices]
    else:
        devs = list(devices)
        if not devs:
            raise ValueError("empty device sequence")
    if len(devs) == 1:
        return None
    return jax.sharding.Mesh(np.array(devs), (GRID_AXIS,))


def simulate_core(st: Static, wl: WLArrays, cfg, knobs_or_key, key=None
                  ) -> SimResult:
    """Jitted core.  Two call forms:

    * new:    ``simulate_core(st, wl, structure, knobs, key)``
    * legacy: ``simulate_core(st, wl, sim_params, key)`` — the flat
      :class:`SimParams` is split internally; knob values are traced, so
      repeat calls with different knob values reuse one compilation.

    Dispatches through the grid core as a 1x1 grid: every entry point
    runs the SAME compiled program family, which keeps single runs
    bitwise-consistent with grid slices (separately-compiled unbatched
    programs can differ by ~1 ulp through XLA fusion reassociation).
    """
    if isinstance(cfg, SimParams):
        if key is not None:
            raise TypeError("legacy form is simulate_core(st, wl, cfg, key)")
        resolve_share_policy(cfg)    # full static validation (pq_on conflicts)
        struct, knobs = cfg.split()
        key = knobs_or_key
    else:
        struct, knobs = cfg, knobs_or_key
        _check_pq_conflict(struct, knobs.pq_on)
    res = _grid_core(jax.tree.map(lambda x: x[None], st), wl, struct,
                     jax.tree.map(lambda x: x[None], knobs), key[None])
    return jax.tree.map(lambda x: x[0, 0], res)


def _check_pq_conflict(struct: SimStructure, pq_on) -> None:
    """Same conflict rule ``resolve_share_policy`` enforces for static
    configs: the pq_on gate overrides the base policy at runtime, so a
    pq point under a wfq/drr structure would silently run strict
    priority.  Knob values are concrete pre-jit, so this is checkable."""
    if struct.share_policy not in ("proportional", "pq") and \
            bool(np.any(np.asarray(pq_on))):
        raise ValueError(
            f"pq_on=True conflicts with share_policy="
            f"{struct.share_policy!r}; use pq only over a "
            "proportional-base structure")


# ---------------------------------------------- windowed checkpoint / resume
def _window_lanes(sts: Static, wl: WLArrays, kns: RuntimeKnobs,
                  sims: SimState, *, struct: SimStructure,
                  n_ticks: int):
    """vmap the windowed engine body over a flat lane axis — the same
    per-lane program structure as `_lanes_impl`, so windowed lanes stay
    bitwise-consistent with closed-form grid lanes."""
    def one(st, kn, sim):
        cfg = merge_params(struct, kn)
        ctx = make_ctx(st, wl, cfg.window)
        return _window_body(ctx, cfg, sim, n_ticks)

    return jax.vmap(one)(sts, kns, sims)


_window_core = functools.partial(
    jax.jit, static_argnames=("struct", "n_ticks"))(_window_lanes)


def init_state(st: Static, wl: WLArrays, struct: SimStructure,
               key: jax.Array | int = 0) -> SimState:
    """Build the tick-0 :class:`~repro.core.netsim.params.SimState` of a
    simulation: the public checkpoint that :func:`run_window` advances.

    ``key`` seeds the DCQCN coin flips — pass the ``jax.random.PRNGKey``
    you would hand :func:`simulate_core` (an int is promoted for you).
    """
    if struct.share_policy not in SHARE_POLICIES:
        raise ValueError(
            f"unknown share policy {struct.share_policy!r}; "
            f"have {sorted(SHARE_POLICIES)}")
    if not isinstance(key, jax.Array):
        key = jax.random.PRNGKey(int(key))
    return SimState(tick=jnp.int32(0),
                    engine=_engine_init(st, wl, struct.window, key))


@functools.partial(jax.jit, static_argnames=("window",))
def _engine_init(st: Static, wl: WLArrays, window: int,
                 key: jax.Array) -> EngineState:
    """The tick-0 engine state as one compiled program: run eagerly, the
    context's route tables and the state's fills would each dispatch (and,
    the first time, compile) an op of their own."""
    return engine_init_state(make_ctx(st, wl, window), key)


def run_window(st: Static, wl: WLArrays, struct: SimStructure,
               knobs: RuntimeKnobs, state: SimState, n_ticks: int
               ) -> tuple[SimState, WindowSamples]:
    """Advance a checkpointed simulation by ``n_ticks`` ticks.

    The windowed core of the engine: one ``lax.scan`` chunk, compiled
    once per ``(struct, n_ticks)`` and reused across calls — knob value
    changes between windows never retrace (the PR-2 contract), so an
    online controller can retune :class:`RuntimeKnobs` every window for
    free.  ``n_ticks`` must be a positive multiple of
    ``struct.record_every`` (windows never split a record period, which
    is what makes split-run sample series concatenate exactly).

    Dispatches as a 1-lane vmapped program (like every other entry
    point), so resumed runs are bit-for-bit identical to one-shot
    :func:`simulate` outputs: integer outputs and ``ts_alpha_max``
    match exactly, including under the fused pallas backend with
    ``tick_window``/``blk`` tiling active.

    Returns ``(state', samples)`` where ``samples`` is a
    :class:`WindowSamples` covering this window's record periods.

    Profiler spans: ``netsim.window.batch`` (the leading lane axis put
    on), ``netsim.window.launch`` (the jitted call), and
    ``netsim.window.unbatch`` (the lane axis taken off).
    """
    _check_pq_conflict(struct, knobs.pq_on)
    if struct.backend not in BACKENDS:
        raise ValueError(
            f"unknown tick backend {struct.backend!r}; have {BACKENDS}")
    if struct.share_policy not in SHARE_POLICIES:
        raise ValueError(
            f"unknown share policy {struct.share_policy!r}; "
            f"have {sorted(SHARE_POLICIES)}")
    R = struct.record_every
    n_ticks = int(n_ticks)
    if n_ticks <= 0 or n_ticks % R:
        raise ValueError(
            f"n_ticks must be a positive multiple of record_every={R} "
            f"(samples are taken on the record grid), got {n_ticks}")
    with TraceAnnotation("netsim.window.batch"):
        sts, kns, sims = jax.tree.map(lambda x: x[None], (st, knobs, state))
    with TraceAnnotation("netsim.window.launch"):
        sim, samples = _window_core(sts, wl, kns, sims, struct=struct,
                                    n_ticks=n_ticks)
    with TraceAnnotation("netsim.window.unbatch"):
        return (jax.tree.map(lambda x: x[0], sim),
                WindowSamples(*(x[0] for x in samples)))


# ------------------------------------------------------------ entry points
def _resolve_routing(cfg, routing: str):
    """Routing modes: 'ecmp' (per-step re-hash, default), 'ecmp_flow'
    (persistent per-flow paths), 'balanced' (static round-robin).
    Works on SimParams and SimStructure alike."""
    if routing == "ecmp":
        return cfg._replace(per_step_ecmp=True), "ecmp"
    if routing == "ecmp_flow":
        return cfg._replace(per_step_ecmp=False), "ecmp"
    if routing == "balanced":
        return cfg._replace(per_step_ecmp=False), "balanced"
    raise ValueError(routing)


def simulate(topo: Topology, wl: Workload, cfg: SimParams,
             routing: str = "ecmp", seed: int = 0,
             bg_base: np.ndarray | None = None,
             bg_amp: np.ndarray | None = None,
             bg_period: float = 1e-3, bg_duty: float = 0.0,
             job_weight: np.ndarray | None = None) -> SimResult:
    """Single-run entry point."""
    cfg, mode = _resolve_routing(cfg, routing)
    st = build_static(topo, wl, mode, seed, bg_base, bg_amp, bg_period,
                      bg_duty, cfg.dt, deploy=cfg.deploy,
                      job_weight=job_weight)
    return simulate_core(st, wl_arrays(wl, cfg.dt), cfg, jax.random.PRNGKey(seed))


def _stacked_statics(topo, wl, mode, seeds, struct, bg_base=None, bg_amp=None,
                     bg_period=1e-3, bg_duty=0.0, job_weight=None):
    statics = [build_static(topo, wl, mode, s, bg_base, bg_amp, bg_period,
                            bg_duty, struct.dt, deploy=struct.deploy,
                            job_weight=job_weight) for s in seeds]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *statics)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    return stacked, keys


def simulate_seeds(topo: Topology, wl: Workload, cfg: SimParams,
                   routing: str, seeds: Sequence[int],
                   devices=None, mesh=None, **bg) -> SimResult:
    """vmap over seeds: both the ECMP path draw and the DCQCN coin flips
    vary.  Result arrays gain a leading ``[S]`` axis.

    Implemented as a 1-point knob grid, so it shares the grid executor's
    compilation cache; ``devices=`` / ``mesh=`` shard the seed lanes
    across devices exactly like grid lanes."""
    resolve_share_policy(cfg)
    struct, knobs = cfg.split()
    res = simulate_grid(topo, wl, struct,
                        jax.tree.map(lambda x: x[None], knobs), seeds,
                        routing=routing, devices=devices, mesh=mesh, **bg)
    return jax.tree.map(lambda x: x[0], res)


def simulate_grid(topo: Topology, wl: Workload, struct: SimStructure,
                  knobs_grid, seeds: Sequence[int] = (0,),
                  routing: str = "ecmp", chunk_knobs: int | None = None,
                  devices=None, mesh=None, **bg) -> SimResult:
    """Batched grid executor: one compile, vmap over knob points x seeds.

    ``knobs_grid`` is a stacked :class:`RuntimeKnobs` pytree (leading axis
    K), or a sequence of per-point ``RuntimeKnobs`` / ``SimParams`` (the
    latter must share ``struct``'s static structure).  Build one from flat
    configs with :func:`grid_from_params`.

    ``devices=`` / ``mesh=`` (see :func:`resolve_grid_mesh`) shard the
    flattened ``K*S`` lane axis across a 1-D device mesh: each device runs
    an equal slice of the lanes through the same single compilation, with
    the lane axis padded (and the padding masked off the result) when the
    lane count doesn't divide the device count.

    The grid is chunked along the knob axis (``chunk_knobs`` points per
    device, default: the whole grid) to bound memory; under a D-device
    mesh one dispatch covers ``chunk_knobs * D`` knob points, so the
    per-device memory bound is preserved.  The last partial chunk is
    padded by repeating the final point, so every chunk has the same
    shape and the engine still traces exactly once.

    Returns a :class:`SimResult` whose arrays carry leading ``[K, S]``
    axes (knob point x seed).

    Profiler spans: ``netsim.grid`` (args ``lanes`` = K*S and ``ticks``)
    around ``netsim.grid.statics`` (the stacked per-seed arrays),
    ``netsim.grid.launch`` (the jitted calls, which return before the
    chip finishes) and, with more than one chunk, ``netsim.grid.concat``.
    """
    if (isinstance(knobs_grid, (list, tuple))
            and not isinstance(knobs_grid, RuntimeKnobs)):
        pts = [p.knobs() if isinstance(p, SimParams) else p
               for p in knobs_grid]
        for p in knobs_grid:
            if isinstance(p, SimParams) and p.structure() != struct:
                raise ValueError(
                    "grid point differs from struct in static fields; "
                    "use grid_from_params to derive a common structure")
        knobs_grid = stack_knobs(pts)
    if struct.share_policy not in SHARE_POLICIES:
        raise ValueError(
            f"unknown share policy {struct.share_policy!r}; "
            f"have {sorted(SHARE_POLICIES)}")
    if struct.backend not in BACKENDS:
        raise ValueError(
            f"unknown tick backend {struct.backend!r}; have {BACKENDS}")
    _check_pq_conflict(struct, knobs_grid.pq_on)
    mesh = resolve_grid_mesh(devices, mesh)
    struct, mode = _resolve_routing(struct, routing)
    K = int(jax.tree.leaves(knobs_grid)[0].shape[0])
    with TraceAnnotation("netsim.grid", lanes=K * len(seeds),
                         ticks=struct.n_ticks):
        with TraceAnnotation("netsim.grid.statics"):
            stacked, keys = _stacked_statics(topo, wl, mode, seeds, struct,
                                             **bg)
            wla = wl_arrays(wl, struct.dt)

        D = 1 if mesh is None else int(mesh.devices.size)
        # chunk_knobs bounds the knob points resident PER DEVICE, so a
        # D-device dispatch covers chunk_knobs * D points at a time.
        per_dev = K if chunk_knobs is None else \
            max(1, min(int(chunk_knobs), K))
        chunk = min(K, per_dev * D)
        pad = (-K) % chunk
        if pad:
            # repeat the final point so the last partial chunk has the same
            # shape as the others (one trace); its padded rows are sliced
            # off the concatenated result below, never observed by callers.
            knobs_grid = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[-1:], pad, axis=0)]), knobs_grid)
        outs = []
        with TraceAnnotation("netsim.grid.launch"):
            for i in range(0, K + pad, chunk):
                kn = jax.tree.map(lambda x: x[i:i + chunk], knobs_grid)
                if mesh is None:
                    outs.append(_grid_core(stacked, wla, struct, kn, keys))
                else:
                    outs.append(_sharded_core(stacked, wla, kn, keys,
                                              struct=struct, mesh=mesh))
        if len(outs) == 1:
            return outs[0]
        with TraceAnnotation("netsim.grid.concat"):
            return jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0)[:K], *outs)
