"""Simulator configuration, split along the jit boundary.

The config layer has three faces:

* :class:`SimStructure` — the *static* part: everything that determines
  array shapes or trace-time control flow (tick count, window size,
  sampling period, share-policy name, Symphony deployment tier, routing
  mode).  Hashable, passed to ``jax.jit`` via ``static_argnames``;
  changing any field recompiles.
* :class:`RuntimeKnobs` — the *traced* part: every numeric control knob
  (RED thresholds, DCQCN constants, Symphony gains, on/off gates) as a
  pytree of f32/i32 scalar leaves.  Changing values never recompiles,
  and a stacked ``RuntimeKnobs`` (leading axis ``K``) vmaps a whole
  parameter grid through one compilation of the engine.
* :class:`SimParams` — the backwards-compatible facade: the flat
  NamedTuple every existing caller builds.  :meth:`SimParams.split`
  produces ``(structure, knobs)``; :func:`merge_params` reassembles an
  attribute-compatible view (:class:`EngineParams`) for the stage
  kernels, which read static fields as Python scalars and knob fields
  as (possibly batched) arrays.

Boolean knobs (``sym_on``, ``pq_on``) become 0/1 gates: the engine
always traces both sides and selects, so a single compiled program
serves baseline, PQ, and Symphony points of a grid.

This module also owns the kernel *tiling plan* (:func:`plan_tiling`) and
the trace-time route-table packer (:class:`PackedTables` /
:func:`pack_route_tables`): per-instance dense copies of every table the
tick kernel used to gather from (`routes[inst_flow]`, the ECMP candidate
slab, `chunk_sched[inst_job]`), so the tiled Pallas kernel can stream
them block-by-block and stay gather-free (Mosaic has no vector-gather
lowering).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from ..symphony import SymphonyParams


class SimParams(NamedTuple):
    """Flat simulator config (facade; see module docstring for the split)."""
    dt: float = 10e-6
    n_ticks: int = 20_000
    window: int = 48               # max concurrent steps per slot (W)
    mtu: float = 1000.0            # bytes per "packet" (psn unit)
    record_every: int = 20         # metric sampling period (ticks)
    # RED / ECN (bytes)
    red_kmin: float = 50e3
    red_kmax: float = 100e3
    red_pmax: float = 0.2
    # DCQCN-style rate control
    cc_epoch_ticks: int = 5        # 50 us control epoch
    cc_g: float = 1.0 / 16.0
    cc_rai: float = 5e6            # additive increase (bytes/s) = 40 Mb/s
    cc_rhai: float = 25e6          # hyper increase
    cc_fr_stages: int = 5
    cc_min_rate: float = 1.25e5    # 1 Mb/s floor (paper §5 "soft limit")
    # Symphony
    sym_on: bool = False
    sym: SymphonyParams = SymphonyParams()
    sym_win_ticks: int = 10        # T_win = 100 us
    sym_start_tick: int = 0        # late-start experiments (Fig. 4)
    deploy: str = "tor"            # Symphony tier: "tor" | "all" | "spine"
    # Alternatives / knobs
    pq_on: bool = False            # strict-priority for lagging flows (Fig. 5)
    share_policy: str = "proportional"  # proportional | pq | wfq | drr
    per_step_ecmp: bool = True     # re-hash the 5-tuple every step (§4.7: the
                                   # step index lives in the UDP sport, so each
                                   # step is a distinct flow to ECMP)
    backend: str = "xla"           # tick hot-path backend: "xla" staged ops |
                                   # "pallas" fused kernel (kernels/netsim_tick)
    segsum: str = "scatter"        # kernel segment reductions: "scatter"
                                   # (.at[].add, bitwise reference) | "onehot"
                                   # (dense contractions, the Mosaic shape)
    blk: int | None = None         # instance-axis tile for the onehot kernel
                                   # (None = whole [FW] in one block)
    tick_window: int = 1           # ticks fused per kernel invocation
                                   # (pallas backend; amortizes state HBM
                                   # round trips 1/tick_window)

    def structure(self) -> "SimStructure":
        return SimStructure(
            dt=self.dt, n_ticks=self.n_ticks, window=self.window,
            mtu=self.mtu, record_every=self.record_every,
            share_policy=self.share_policy, deploy=self.deploy,
            per_step_ecmp=self.per_step_ecmp, backend=self.backend,
            segsum=self.segsum, blk=self.blk, tick_window=self.tick_window)

    def knobs(self) -> "RuntimeKnobs":
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        return RuntimeKnobs(
            red_kmin=f32(self.red_kmin), red_kmax=f32(self.red_kmax),
            red_pmax=f32(self.red_pmax),
            cc_epoch_ticks=i32(self.cc_epoch_ticks), cc_g=f32(self.cc_g),
            cc_rai=f32(self.cc_rai), cc_rhai=f32(self.cc_rhai),
            cc_fr_stages=i32(self.cc_fr_stages),
            cc_min_rate=f32(self.cc_min_rate),
            sym_on=i32(self.sym_on),
            sym=SymphonyParams(*(f32(v) for v in self.sym)),
            sym_win_ticks=i32(self.sym_win_ticks),
            sym_start_tick=i32(self.sym_start_tick),
            pq_on=i32(self.pq_on))

    def split(self) -> tuple["SimStructure", "RuntimeKnobs"]:
        return self.structure(), self.knobs()


class SimStructure(NamedTuple):
    """Shape/compile-time structure: hashable, a jit static argument."""
    dt: float = 10e-6
    n_ticks: int = 20_000
    window: int = 48
    mtu: float = 1000.0
    record_every: int = 20
    share_policy: str = "proportional"
    deploy: str = "tor"
    per_step_ecmp: bool = True
    backend: str = "xla"
    segsum: str = "scatter"
    blk: int | None = None
    tick_window: int = 1


class RuntimeKnobs(NamedTuple):
    """Device-traced control knobs: a pytree of f32/i32 scalar leaves.

    Stack along a leading axis (:func:`stack_knobs`) to form a grid that
    ``simulate_grid`` vmaps through a single compilation.
    """
    red_kmin: jax.Array
    red_kmax: jax.Array
    red_pmax: jax.Array
    cc_epoch_ticks: jax.Array
    cc_g: jax.Array
    cc_rai: jax.Array
    cc_rhai: jax.Array
    cc_fr_stages: jax.Array
    cc_min_rate: jax.Array
    sym_on: jax.Array            # 0/1 gate (traced; no recompile to toggle)
    sym: SymphonyParams          # five f32 leaves (k, tau, warmup, sample, amax)
    sym_win_ticks: jax.Array
    sym_start_tick: jax.Array
    pq_on: jax.Array             # 0/1 gate: strict-priority override


class SimState(NamedTuple):
    """The public checkpoint/resume carry of a simulation in flight.

    A pure pytree of device arrays: the tick cursor plus the *full* engine
    scan carry (:class:`~repro.core.netsim.stages.EngineState` — slot,
    instance, link, Symphony, and job state, including the CC PRNG key).
    Produced by ``simulator.init_state``, advanced by
    ``simulator.run_window`` / ``control.SimController.step``, and
    serializable with ``jax.device_get`` — resuming from a checkpointed
    ``SimState`` is bit-for-bit identical to having never paused.

    ``engine`` is typed ``Any`` only to avoid a circular import with
    :mod:`.stages`; it is always an ``EngineState``.
    """
    tick: jax.Array      # i32 scalar: the next tick to execute
    engine: Any          # stages.EngineState — the full tick carry


class EngineParams(NamedTuple):
    """Merged trace-time view handed to the stage kernels.

    Field names match :class:`SimParams`, so stages written against the
    flat config keep working: static fields are Python scalars, knob
    fields are arrays (scalars, or batched under vmap).  Not a jit
    argument — it is assembled inside ``simulate_core`` and closed over
    by the scanned tick function.
    """
    dt: float
    n_ticks: int
    window: int
    mtu: float
    record_every: int
    share_policy: str
    deploy: str
    per_step_ecmp: bool
    backend: str
    segsum: str
    blk: int | None
    tick_window: int
    red_kmin: jax.Array
    red_kmax: jax.Array
    red_pmax: jax.Array
    cc_epoch_ticks: jax.Array
    cc_g: jax.Array
    cc_rai: jax.Array
    cc_rhai: jax.Array
    cc_fr_stages: jax.Array
    cc_min_rate: jax.Array
    sym_on: jax.Array
    sym: SymphonyParams
    sym_win_ticks: jax.Array
    sym_start_tick: jax.Array
    pq_on: jax.Array


def merge_params(struct: SimStructure, knobs: RuntimeKnobs) -> EngineParams:
    return EngineParams(
        dt=struct.dt, n_ticks=struct.n_ticks, window=struct.window,
        mtu=struct.mtu, record_every=struct.record_every,
        share_policy=struct.share_policy, deploy=struct.deploy,
        per_step_ecmp=struct.per_step_ecmp, backend=struct.backend,
        segsum=struct.segsum, blk=struct.blk, tick_window=struct.tick_window,
        **knobs._asdict())


def stack_knobs(knobs: Sequence[RuntimeKnobs]) -> RuntimeKnobs:
    """Stack scalar knob pytrees into one grid pytree with leading axis K."""
    if not knobs:
        raise ValueError("empty knob grid")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *knobs)


def grid_from_params(cfgs: Sequence[SimParams]
                     ) -> tuple[SimStructure, RuntimeKnobs]:
    """Split a list of SimParams into (shared structure, stacked knobs).

    All cfgs must agree on every structural field — a grid sweeps knob
    values through one compiled program, it cannot change shapes.
    """
    if not cfgs:
        raise ValueError("empty parameter grid")
    structs = {cfg.structure() for cfg in cfgs}
    if len(structs) > 1:
        a, b, *_ = structs
        diff = [f for f, x, y in zip(a._fields, a, b) if x != y]
        raise ValueError(
            f"grid points differ in static structure (fields {diff}); "
            "sweep only RuntimeKnobs fields, or run separate grids")
    return cfgs[0].structure(), stack_knobs([cfg.knobs() for cfg in cfgs])


# ------------------------------------------------ kernel tiling + tables
class PackedTables(NamedTuple):
    """Per-instance dense route/chunk/ECMP tables for the tick kernel.

    Every array leads with the flat ``[FW]`` instance axis, so the tiled
    grid kernel can BlockSpec-stream them in ``blk``-row slabs (edge-
    padded like the other per-instance operands) and every former
    ``table[index]`` gather becomes a block-local row read or an
    iota-select.  Packed once per trace by :func:`pack_route_tables`
    (``jnp.repeat`` over the window axis — broadcast + reshape, itself
    gather-free), carried on ``EngineCtx.tables``.
    """
    routes: jax.Array     # [FW, H]    static per-instance route links
    route_dom: jax.Array  # [FW, H]    Symphony domain of each static hop
    cand: jax.Array       # [FW, P, H] ECMP candidate paths per instance
    cand_dom: jax.Array   # [FW, P, H] domains of the candidate hops
    n_paths: jax.Array    # [FW]       valid candidate count per instance
    chunk: jax.Array      # [FW, SEG]  per-instance segment chunk sizes


class PathIndex(NamedTuple):
    """Flow-granular route index tables, ``[F, P, H]`` each: plane ``p`` of
    flow ``f`` is its ``p``-th ECMP candidate path (``P = 1``: the static
    route).  An instance's route is fixed by its flow and its candidate, so
    a per-link or per-(domain, job) value is gathered here once per (flow,
    candidate, hop) and picked per instance (`stages.InstView.on_path`)."""
    links: jax.Array   # link ids
    dom: jax.Array     # Symphony domain of each hop
    dj: jax.Array      # (domain, job) row of each hop


class RoutePaths(NamedTuple):
    """The two routings a tick may take, as :class:`PathIndex` tables."""
    cand: PathIndex    # [F, P, H] ECMP candidates (per-step ECMP)
    static: PathIndex  # [F, 1, H] static per-flow routes


def route_paths(st, wl) -> RoutePaths:
    """Index the candidate and static routes once: links, their Symphony
    domains, and the (domain, job) rows ``dom * J + job``.

    ``st`` needs ``routes``/``path_table``/``link_dom``; ``wl`` needs
    ``job``/``n_phases`` (duck-typed like :func:`pack_route_tables`).
    """
    J = int(wl.n_phases.shape[0])

    def index(links):
        dom = st.link_dom[links]
        return PathIndex(links=links, dom=dom,
                         dj=dom * J + wl.job[:, None, None])

    return RoutePaths(cand=index(st.path_table),
                      static=index(st.routes[:, None, :]))


def pack_route_tables(st, wl, window: int,
                      paths: RoutePaths | None = None) -> PackedTables:
    """Expand the per-flow/per-job tables to the ``[FW]`` instance axis.

    ``st`` needs ``routes``/``path_table``/``n_paths``/``link_dom``;
    ``wl`` needs ``job``/``n_phases``/``chunk_sched`` (duck-typed:
    `simulator.Static` and `stages.WLArrays`).  The route rows repeat
    ``paths`` (:func:`route_paths` when None).  The window expansion is
    ``jnp.repeat(x, W, axis=0)`` — row ``f*W + w`` holds flow ``f``'s
    table, matching the ``inst_flow``/``inst_job`` layout of
    `stages.make_ctx`.
    """
    W = int(window)
    cand, static = route_paths(st, wl) if paths is None else paths

    def per_inst(x):
        return jnp.repeat(x, W, axis=0)

    return PackedTables(
        routes=per_inst(static.links[:, 0]),
        route_dom=per_inst(static.dom[:, 0]),
        cand=per_inst(cand.links),
        cand_dom=per_inst(cand.dom),
        n_paths=per_inst(st.n_paths),
        chunk=per_inst(wl.chunk_sched[wl.job]),
    )


def plan_tiling(FW: int, blk: int | None, segsum: str,
                tick_window: int) -> int | None:
    """Validate and normalize the kernel tiling plan for an ``[FW]``
    instance axis: returns the effective ``blk`` (``None`` = untiled).

    * ``blk >= FW`` normalizes to untiled (one whole-array block).
    * ``blk`` tiling requires the dense ``segsum="onehot"`` reductions —
      the scatter variant cannot accumulate per-block partials without
      the vector scatters the tiling exists to eliminate.
    * ``tick_window > 1`` dispatches through the multi-tick window
      kernel, which keeps the whole ``[FW]`` axis (and the packed route
      tables) VMEM-resident across its in-kernel ``fori_loop`` — so the
      single-tick grid tiling normalizes away and ``blk`` combines
      freely with windowing (the combined ``blk x tick_window`` config
      is golden-tested).
    """
    if blk is None:
        return None
    if blk < 1:
        raise ValueError(f"blk must be >= 1, got {blk}")
    if int(blk) >= FW:
        return None
    if segsum != "onehot":
        raise ValueError(
            f"blk={blk} tiling requires segsum='onehot'; "
            f"got segsum={segsum!r}")
    if tick_window > 1:
        return None
    return int(blk)
