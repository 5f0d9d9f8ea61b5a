"""Composable per-tick stages of the fluid network-simulation engine.

The simulator's tick is decomposed into small, individually-testable pure
functions over an :class:`EngineCtx` (static per-run arrays + dims) and an
:class:`EngineState` (the `lax.scan` carry).  :func:`engine_tick` composes
them; `simulator.simulate_core` wraps that composition in one scan so the
whole run still jits/vmaps as a single program.

Stage order (one tick):

1. :func:`stage_starts`        — segment barrier + ring dependency gating
2. :func:`instance_view`       — per-instance arrays incl. route selection
                                 (per-step ECMP re-hash over the candidate
                                 path table, any hop count)
3. :func:`stage_share`         — bandwidth sharing: ``proportional`` fluid
                                 max-min approximation, ``pq`` 2-class
                                 strict priority, ``wfq`` weighted fair,
                                 ``drr`` deficit round-robin; the traced
                                 ``pq_on`` gate overrides at runtime
4. :func:`stage_queues`        — queue integration + RED profile
5. :func:`stage_marking`       — RED x Symphony selective marking -> lambda
6. :func:`stage_progress`      — byte progress, completions, finish times
7. :func:`stage_symphony`      — per-(domain, job) state block updates
8. :func:`stage_rate_control`  — DCQCN-style epoch update
9. :func:`stage_segments`      — segment barriers and job finish
10. :func:`stage_metrics`      — sampled observables

The ``cfg`` argument of every stage is attribute-compatible with both the
flat :class:`~repro.core.netsim.params.SimParams` (all-Python legacy view)
and the merged :class:`~repro.core.netsim.params.EngineParams`, whose knob
fields (RED/CC/Symphony constants, ``sym_on``/``pq_on`` gates) are traced
arrays — so the same stage code serves single runs and vmapped knob grids
without retracing per parameter point.

Each stage runs under a ``jax.named_scope`` of its own (``netsim.starts``,
``netsim.instance_view``, ``netsim.share``, ``netsim.queues``,
``netsim.marking``, ``netsim.progress``, ``netsim.symphony``,
``netsim.rate_control``, ``netsim.segments``, ``netsim.metrics``), so the
ops it lowers to carry that name in their HLO ``op_name`` metadata and a
profiler trace attributes device time to stages.  The scope is trace-time
metadata only: the computed values and the compile count are unchanged.

A per-hop lookup (a per-link or per-(domain, job) value at every hop of
every instance's path) goes through :meth:`InstView.on_path`: it gathers
once per (flow, ECMP candidate, hop) and picks each instance's candidate
with exact selects, since an instance's route is fixed by its flow and
its candidate.  The scatters onto links and (domain, job) rows stay per
instance, so no sum changes order.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..symphony import marking_probability
from .params import (PackedTables, PathIndex, RoutePaths,
                     pack_route_tables, route_paths)

# Wire-step encoding: global segment index * WIRE_SEG + step-within-segment.
# Monotone across segments; comparable across flows inside a segment.
WIRE_SEG = 4096
I32MAX = np.iinfo(np.int32).max
# Python int, not jnp.int32: promotes weakly to int32 in every use
# (identical values), and keeps stage code callable inside Pallas kernel
# bodies, which cannot capture device-array constants (the multi-tick
# window kernel replays the stages per tick).
BIG = 2**30


def _scoped(name: str):
    """Run the decorated stage under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


class WLArrays(NamedTuple):
    src: jax.Array; dst: jax.Array; pred: jax.Array; job: jax.Array
    phase: jax.Array; sps: jax.Array; pass_steps: jax.Array
    total_steps: jax.Array
    n_phases: jax.Array; n_segs: jax.Array; chunk_sched: jax.Array
    gap_ticks: jax.Array; start_ticks: jax.Array
    step_offset: jax.Array; fstart_ticks: jax.Array
    # dependency-triggered arrivals (all [J] i32; trig_job=-1 => fixed start)
    trig_job: jax.Array; trig_seg: jax.Array; trig_delay_ticks: jax.Array


class EngineState(NamedTuple):
    """The scan carry: slot, instance, link, Symphony, and job state."""
    # slot level [F]
    next_step: jax.Array; done_upto: jax.Array; finish: jax.Array
    # instance level [F, W]
    step_of: jax.Array; sent: jax.Array
    rate: jax.Array; target: jax.Array; alpha_cc: jax.Array; stage: jax.Array
    lam: jax.Array                     # accumulated expected marks this epoch
    # link level [L+1]
    q: jax.Array
    # Symphony per (link-domain, job), flattened [(D+1) * J]
    s_stepmin: jax.Array; s_psnwin: jax.Array; s_alpha: jax.Array
    s_cnt: jax.Array; s_cntop: jax.Array
    # job level [J]
    seg_idx: jax.Array; seg_ready: jax.Array; job_finish: jax.Array
    key: jax.Array


@dataclass(frozen=True)
class EngineCtx:
    """Static (trace-time) context: dims, broadcast views, device arrays.

    Not a pytree — it is closed over by the scanned tick function, so all
    integer fields stay Python ints and keep shapes static.
    """
    st: Any                  # Static device arrays (routes, caps, domains, ..)
    wl: WLArrays
    F: int; J: int; W: int; L: int; H: int; D: int
    fidx: jax.Array          # [F]
    nph_f: jax.Array         # [F] phases per pass of each flow's job
    line_rate: jax.Array     # [F] access-link rate
    inst_job: jax.Array      # [FW]
    inst_flow: jax.Array     # [FW]
    sps_i: jax.Array; phase_i: jax.Array; nph_i: jax.Array; off_i: jax.Array
    # flow-granular route tables (params.RoutePaths), built once per
    # dispatch outside the scan: every per-hop lookup goes through them
    paths: RoutePaths
    # per-instance dense route/chunk/ECMP tables (params.PackedTables):
    # the gather-free tiled kernel streams these instead of gathering.
    tables: PackedTables | None = None

    @property
    def FW(self) -> int:
        return self.F * self.W

    def per_inst(self, x: jax.Array) -> jax.Array:
        """Per-flow ``[F]`` -> per-instance ``[FW]`` (row ``f*W + w`` is
        flow ``f``): a broadcast, not a gather."""
        return jnp.repeat(x, self.W)

    def chunk_of(self, job_ids, seg):
        max_seg = int(self.wl.chunk_sched.shape[1])
        return self.wl.chunk_sched[job_ids, jnp.clip(seg, 0, max_seg - 1)]


def make_ctx(st, wl: WLArrays, window: int,
             tables: PackedTables | None = None) -> EngineCtx:
    F = int(wl.src.shape[0])
    J = int(wl.n_phases.shape[0])
    W = window
    L = int(st.cap.shape[0]) - 1
    H = int(st.routes.shape[-1])
    D = int(st.dom_pad.shape[-1]) - 1   # null domain id (static)
    FW = F * W
    nph_f = wl.n_phases[wl.job]
    fidx = jnp.arange(F)
    paths = route_paths(st, wl)
    return EngineCtx(
        st=st, wl=wl, F=F, J=J, W=W, L=L, H=H, D=D,
        fidx=fidx, nph_f=nph_f,
        line_rate=st.cap[st.routes[:, 0]],
        inst_job=jnp.broadcast_to(wl.job[:, None], (F, W)).reshape(FW),
        inst_flow=jnp.broadcast_to(fidx[:, None], (F, W)).reshape(FW),
        sps_i=jnp.broadcast_to(wl.sps[:, None], (F, W)).reshape(FW),
        phase_i=jnp.broadcast_to(wl.phase[:, None], (F, W)).reshape(FW),
        nph_i=jnp.broadcast_to(nph_f[:, None], (F, W)).reshape(FW),
        off_i=jnp.broadcast_to(wl.step_offset[:, None], (F, W)).reshape(FW),
        paths=paths,
        tables=pack_route_tables(st, wl, W, paths) if tables is None
        else tables,
    )


def init_state(ctx: EngineCtx, key: jax.Array) -> EngineState:
    F, W, J, L, D = ctx.F, ctx.W, ctx.J, ctx.L, ctx.D
    DJ = (D + 1) * J
    wl = ctx.wl
    return EngineState(
        next_step=jnp.zeros(F, jnp.int32),
        done_upto=jnp.zeros(F, jnp.int32),
        finish=jnp.full(F, I32MAX, jnp.int32),
        step_of=jnp.full((F, W), -1, jnp.int32),
        sent=jnp.zeros((F, W), jnp.float32),
        rate=jnp.zeros((F, W), jnp.float32) + ctx.line_rate[:, None],
        target=jnp.zeros((F, W), jnp.float32) + ctx.line_rate[:, None],
        alpha_cc=jnp.ones((F, W), jnp.float32),
        stage=jnp.zeros((F, W), jnp.int32),
        lam=jnp.zeros((F, W), jnp.float32),
        q=jnp.zeros(L + 1, jnp.float32),
        s_stepmin=jnp.zeros(DJ, jnp.int32),
        s_psnwin=jnp.zeros(DJ, jnp.float32),
        s_alpha=jnp.ones(DJ, jnp.float32),
        s_cnt=jnp.zeros(DJ, jnp.float32),
        s_cntop=jnp.zeros(DJ, jnp.float32),
        seg_idx=jnp.zeros(J, jnp.int32),
        # Triggered jobs hold at the I32MAX sentinel until stage_segments
        # releases them (dependency satisfied); fixed-start jobs keep the
        # legacy start+gap release tick.
        seg_ready=jnp.where(wl.trig_job >= 0, I32MAX,
                            wl.start_ticks + wl.gap_ticks),
        job_finish=jnp.full(J, I32MAX, jnp.int32),
        key=key,
    )


def seg_global(c, sps, phase, n_phases):
    """Global segment index of local step c for a flow slot."""
    return (c // sps) * n_phases + phase


def wire_step(c, sps, phase, n_phases):
    """Monotone wire-step encoding (§3.2) of local step c."""
    return seg_global(c, sps, phase, n_phases) * WIRE_SEG + (c % sps)


# ------------------------------------------------------------- 1. starts
class Starts(NamedTuple):
    next_step: jax.Array
    step_of: jax.Array; sent: jax.Array
    rate: jax.Array; target: jax.Array; alpha_cc: jax.Array
    stage: jax.Array; lam: jax.Array
    can: jax.Array


@_scoped("netsim.starts")
def stage_starts(ctx: EngineCtx, state: EngineState, tick) -> Starts:
    """Gate new step-sends on segment barrier + ring data dependency + slot
    availability, and initialize the window slots of the started steps."""
    wl, fidx, W = ctx.wl, ctx.fidx, ctx.W
    s_next = state.next_step
    seg_of_next = seg_global(s_next, wl.sps, wl.phase, ctx.nph_f)
    seg_ok = (seg_of_next == state.seg_idx[wl.job]) & \
             (tick >= state.seg_ready[wl.job])
    # Ring data dependency. Within a collective, send(s) needs only
    # recv(s-1) == predecessor's *step s-1* send completed (steps carry
    # independent chunks, so no contiguity requirement).  At a collective
    # boundary (s % pass_steps == 0) the node needs its previous
    # collective complete: all own sends and all receives done.
    boundary = (s_next % wl.pass_steps) == 0
    w_prev = (s_next - 1) % W
    ps_prev = state.step_of[wl.pred, w_prev]
    prev_chunk = ctx.chunk_of(
        wl.job, seg_global(s_next - 1, wl.sps, wl.phase, ctx.nph_f))
    pred_prev_done = (state.done_upto[wl.pred] >= s_next) | \
        (ps_prev > s_next - 1) | \
        ((ps_prev == s_next - 1) &
         (state.sent[wl.pred, w_prev] >= prev_chunk))
    pass_done = (state.done_upto >= s_next) & \
        (state.done_upto[wl.pred] >= s_next)
    ring_ok = jnp.where(boundary, (s_next == 0) | pass_done, pred_prev_done)
    ring_ok &= tick >= wl.fstart_ticks
    w_next = s_next % W
    slot = state.step_of[fidx, w_next]
    slot_free = (slot < 0) | (slot < state.done_upto)
    can = (s_next < wl.total_steps) & seg_ok & ring_ok & slot_free

    def upd(arr, val):
        return arr.at[fidx, w_next].set(
            jnp.where(can, val, arr[fidx, w_next]))

    return Starts(
        next_step=jnp.where(can, s_next + 1, s_next),
        step_of=upd(state.step_of, s_next),
        sent=upd(state.sent, 0.0),
        rate=upd(state.rate, ctx.line_rate),
        target=upd(state.target, ctx.line_rate),
        alpha_cc=upd(state.alpha_cc, 1.0),
        stage=upd(state.stage, 0),
        lam=upd(state.lam, 0.0),
        can=can,
    )


# ------------------------------------------------------- 2. instance view
def per_hop(x: jax.Array, H: int) -> jax.Array:
    """Expand a per-instance [FW] array to one entry per (instance, hop)
    [FW*H], aligned with ``InstView.flat_links`` / ``.djf``."""
    return jnp.repeat(x, H)


def link_scatter_sum(flat_links: jax.Array, vals: jax.Array, H: int,
                     n_rows: int) -> jax.Array:
    """Scatter-add per-instance values onto their path links: the one
    segment-sum every share policy (and the fused kernel) is built on."""
    return jnp.zeros(n_rows).at[flat_links].add(per_hop(vals, H))


def pick_plane(table: jax.Array, choice: jax.Array | None,
               W: int) -> jax.Array:
    """Per-flow ``[F, P, H]`` table -> per-instance ``[F*W, H]``: row
    ``f*W + w`` takes plane ``choice[f*W + w]`` of flow ``f`` (``choice``
    None: the one plane of a ``P = 1`` table).  A chain of selects, so the
    pick is exact for ints and floats alike; ``choice`` is always below
    the flow's candidate count."""
    F, P, H = table.shape
    plane = functools.partial(jax.lax.index_in_dim, table, axis=1)
    acc = plane(P - 1)                      # [F, 1, H]
    if choice is not None:
        c = choice.reshape(F, W, 1)
        for p in range(P - 2, -1, -1):
            acc = jnp.where(c == p, plane(p), acc)
    return jnp.broadcast_to(acc, (F, W, H)).reshape(F * W, H)


class InstView(NamedTuple):
    """Flattened [FW] per-instance arrays for this tick.

    The per-hop expansion (``jnp.repeat(..., H)``) and the flat-link
    scatter setup are precomputed once here and consumed through
    :meth:`per_hop` / :meth:`link_sum` / :meth:`path_min`, so every share
    policy — and the fused ``netsim_tick`` kernel — shares one index set
    instead of rebuilding it per policy.  :meth:`on_path` reads a per-link
    or per-(domain, job) array at every hop.
    """
    istep: jax.Array; isent: jax.Array; irate: jax.Array
    iseg: jax.Array; ichunk: jax.Array; iwire: jax.Array; ipsn: jax.Array
    occupied: jax.Array; retired: jax.Array; complete: jax.Array
    active: jax.Array
    iroute: jax.Array        # [FW, H] link ids
    flat_links: jax.Array    # [FW*H]
    idom: jax.Array          # [FW, H] Symphony domain per hop
    dj: jax.Array            # [FW, H] (domain, job) row ids
    djf: jax.Array           # [FW*H]
    # The flow-granular route tables of this tick's routing and each
    # instance's plane in them (choice None: the static routes, P = 1).
    paths: PathIndex
    choice: jax.Array | None  # [FW]

    @property
    def H(self) -> int:
        return int(self.iroute.shape[-1])

    def per_hop(self, x: jax.Array) -> jax.Array:
        """[FW] -> [FW*H], aligned with ``flat_links``."""
        return per_hop(x, self.H)

    def link_sum(self, ctx: "EngineCtx", vals: jax.Array) -> jax.Array:
        """Scatter-add per-instance ``vals`` onto the [L+1] link axis."""
        return link_scatter_sum(self.flat_links, vals, self.H, ctx.L + 1)

    def on_path(self, x: jax.Array, *, dj: bool = False) -> jax.Array:
        """``x`` at each instance's hops, ``[FW, H]``: ``x`` is a per-link
        ``[L+1]`` array, or with ``dj`` a per-(domain, job) ``[DJ]`` one.
        Gathered once per (flow, candidate, hop), picked per instance."""
        table = self.paths.dj if dj else self.paths.links
        return pick_plane(x[table], self.choice,
                          self.istep.shape[0] // table.shape[0])

    def path_min(self, per_link: jax.Array) -> jax.Array:
        """Worst per-hop value along each instance's path: [L+1] -> [FW]."""
        return self.on_path(per_link).min(axis=1)


def route_choice(ctx: EngineCtx, istep) -> jax.Array:
    """Each instance's ECMP candidate index ``[FW]``.  The step index is
    part of the 5-tuple (paper §4.7: it lives in the UDP sport), so each
    step re-rolls its hash over the flow's candidate paths.  Elementwise."""
    st = ctx.st
    h = (ctx.inst_flow.astype(jnp.uint32) * jnp.uint32(2654435761)
         + jnp.maximum(istep, 0).astype(jnp.uint32) * jnp.uint32(40503)
         + (st.seed.astype(jnp.uint32) + 1) * jnp.uint32(2246822519))
    h = (h ^ (h >> 13)) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    n_paths = ctx.per_inst(st.n_paths).astype(jnp.uint32)
    return (h % n_paths).astype(jnp.int32)


def select_routes(ctx: EngineCtx, istep, per_step_ecmp: bool) -> jax.Array:
    """Per-instance routes gathered per instance: with per-step ECMP the
    hashed candidate (:func:`route_choice`), otherwise the static per-flow
    paths.  The reference that :func:`instance_view`'s plane pick and the
    kernel's route selection both equal; the tick itself does not call it."""
    if not per_step_ecmp:
        return ctx.st.routes[ctx.inst_flow]
    return ctx.st.path_table[ctx.inst_flow, route_choice(ctx, istep)]


def _pick_col(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[arange(N), idx]`` for an ``[N, C]`` table of non-negative
    values: one entry per row survives the mask, so the sum is exact."""
    hit = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1) == idx[:, None]
    return jnp.where(hit, table, 0).sum(axis=1)


@_scoped("netsim.instance_view")
def instance_view(ctx: EngineCtx, starts: Starts, state: EngineState,
                  mtu: float, per_step_ecmp: bool) -> InstView:
    """Assemble the per-instance view.  Each instance's route, domains and
    (domain, job) rows are picked from the flow-granular tables
    (:class:`PathIndex`) by its ECMP candidate, and every per-hop lookup
    goes through them (:meth:`InstView.on_path`): F*P*H indices gathered
    instead of F*W*H.  On the kernel path this recomputes the candidate the
    kernel hashed, so one choice decides both the scatters and the
    lookups."""
    J, W = ctx.J, ctx.W
    istep = starts.step_of.reshape(ctx.FW)
    isent = starts.sent.reshape(ctx.FW)
    irate = starts.rate.reshape(ctx.FW)
    iseg = seg_global(istep, ctx.sps_i, ctx.phase_i, ctx.nph_i)
    SEG = int(ctx.wl.chunk_sched.shape[1])
    ichunk = _pick_col(ctx.tables.chunk, jnp.clip(iseg, 0, SEG - 1))
    iwire = wire_step(istep, ctx.sps_i, ctx.phase_i, ctx.nph_i) + ctx.off_i
    occupied = istep >= 0
    retired = occupied & (istep < ctx.per_inst(state.done_upto))
    complete = occupied & (isent >= ichunk)
    active = occupied & ~complete & ~retired
    paths = ctx.paths.cand if per_step_ecmp else ctx.paths.static
    choice = route_choice(ctx, istep) if per_step_ecmp else None
    iroute = pick_plane(paths.links, choice, W)
    idom = pick_plane(paths.dom, choice, W)
    dj = idom * J + ctx.inst_job[:, None]
    return InstView(
        istep=istep, isent=isent, irate=irate, iseg=iseg, ichunk=ichunk,
        iwire=iwire, ipsn=isent / mtu,
        occupied=occupied, retired=retired, complete=complete, active=active,
        iroute=iroute, flat_links=iroute.reshape(-1),
        idom=idom, dj=dj, djf=dj.reshape(-1), paths=paths, choice=choice,
    )


# ---------------------------------------------------- 3. bandwidth sharing
def background_load(ctx: EngineCtx, tick) -> jax.Array:
    st = ctx.st
    bg_on = (tick % st.bg_period_ticks).astype(jnp.float32) < \
        st.bg_duty * st.bg_period_ticks.astype(jnp.float32)
    return st.bg_base + jnp.where(bg_on, st.bg_amp, 0.0)


class ShareResult(NamedTuple):
    eff: jax.Array       # [FW] delivered bytes/s per instance
    offered: jax.Array   # [L+1] offered load per link (drives the queues)


def share_proportional(ctx: EngineCtx, cfg, inst: InstView, tick
                       ) -> ShareResult:
    """Fluid max-min approximation: every link scales its offered load by
    cap/offered; an instance gets the worst scale along its path."""
    st = ctx.st
    w_rate = jnp.where(inst.active, inst.irate, 0.0)
    bg = background_load(ctx, tick)
    offered = inst.link_sum(ctx, w_rate) + bg
    s_l = jnp.minimum(1.0, st.cap / jnp.maximum(offered, 1.0))
    return ShareResult(eff=w_rate * inst.path_min(s_l), offered=offered)


def share_pq(ctx: EngineCtx, cfg, inst: InstView, tick) -> ShareResult:
    """2-class strict priority: the job's oldest active step is high class
    (Fig. 5 "PQ"); the low class shares what remains."""
    st, J = ctx.st, ctx.J
    w_rate = jnp.where(inst.active, inst.irate, 0.0)
    bg = background_load(ctx, tick)
    job_min_wire = jnp.full(J, BIG).at[ctx.inst_job].min(
        jnp.where(inst.active, inst.iwire, BIG))
    is_hi = inst.active & (inst.iwire <=
                           ctx.per_inst(job_min_wire[ctx.wl.job]))
    hi_rate = jnp.where(is_hi, inst.irate, 0.0)
    off_hi = inst.link_sum(ctx, hi_rate) + bg
    s_hi = jnp.minimum(1.0, st.cap / jnp.maximum(off_hi, 1.0))
    rem = jnp.maximum(st.cap - off_hi * s_hi, 0.0)
    lo_rate = jnp.where(inst.active & ~is_hi, inst.irate, 0.0)
    off_lo = inst.link_sum(ctx, lo_rate)
    s_lo = rem / jnp.maximum(off_lo, 1.0)
    share = jnp.where(is_hi[:, None], inst.on_path(s_hi),
                      jnp.minimum(1.0, inst.on_path(s_lo)))
    eff_scale = share.min(axis=1)
    return ShareResult(eff=w_rate * eff_scale, offered=off_hi + off_lo)


def share_wfq(ctx: EngineCtx, cfg, inst: InstView, tick) -> ShareResult:
    """Weighted fair sharing: each link divides its post-background capacity
    over active instances proportionally to their job's weight
    (``Static.job_weight``); an instance is capped at its own rate and takes
    the worst per-hop allowance (one-shot water-filling approximation)."""
    st = ctx.st
    w_rate = jnp.where(inst.active, inst.irate, 0.0)
    bg = background_load(ctx, tick)
    wgt = st.job_weight[ctx.inst_job]
    w_act = jnp.where(inst.active, wgt, 0.0)
    wsum = inst.link_sum(ctx, w_act)
    avail = jnp.maximum(st.cap - bg, 0.0)
    fair = avail / jnp.maximum(wsum, 1e-9)           # bytes/s per unit weight
    allowed = wgt[:, None] * inst.on_path(fair)      # [FW, H]
    eff = jnp.minimum(w_rate, allowed.min(axis=1))
    return ShareResult(eff=eff, offered=inst.link_sum(ctx, w_rate) + bg)


def share_drr(ctx: EngineCtx, cfg, inst: InstView, tick) -> ShareResult:
    """Deficit round-robin (fluid approximation): every link serves its
    active instances an equal per-round quantum regardless of job, and the
    deficit left by rate-limited instances is redistributed to the still-
    hungry ones in a second round (two-round water-filling)."""
    st = ctx.st
    w_rate = jnp.where(inst.active, inst.irate, 0.0)
    bg = background_load(ctx, tick)
    n_act = inst.link_sum(ctx, inst.active.astype(jnp.float32))
    avail = jnp.maximum(st.cap - bg, 0.0)
    quantum = avail / jnp.maximum(n_act, 1.0)
    take1 = jnp.minimum(w_rate, inst.path_min(quantum))
    used = inst.link_sum(ctx, take1)
    want = inst.active & (take1 < w_rate)
    n_want = inst.link_sum(ctx, want.astype(jnp.float32))
    bonus = jnp.maximum(avail - used, 0.0) / jnp.maximum(n_want, 1.0)
    take2 = jnp.where(want,
                      jnp.minimum(w_rate - take1, inst.path_min(bonus)), 0.0)
    return ShareResult(eff=take1 + take2,
                       offered=inst.link_sum(ctx, w_rate) + bg)


SHARE_POLICIES: dict[str, Callable[..., ShareResult]] = {
    "proportional": share_proportional,
    "pq": share_pq,
    "wfq": share_wfq,
    "drr": share_drr,
}


# --------------------------------------------------------- 4. queues + RED
@_scoped("netsim.queues")
def stage_queues(ctx: EngineCtx, cfg, q_prev, offered):
    """Integrate per-link queues and derive the RED marking profile."""
    q = jnp.maximum(q_prev + (offered - ctx.st.cap) * cfg.dt, 0.0)
    q = q.at[ctx.L].set(0.0)
    p_red = jnp.clip((q - cfg.red_kmin) / (cfg.red_kmax - cfg.red_kmin),
                     0.0, 1.0) * cfg.red_pmax
    return q, p_red


# ------------------------------------------------------------- 5. marking
@_scoped("netsim.marking")
def stage_marking(ctx: EngineCtx, cfg, state: EngineState, inst: InstView,
                  p_red, eff, lam, tick):
    """Combine RED with Symphony's selective marking along each path into
    the per-instance expected-mark accumulator lambda."""
    D = ctx.D
    sm = inst.on_path(state.s_stepmin, dj=True)
    pw = inst.on_path(state.s_psnwin, dj=True)
    al = inst.on_path(state.s_alpha, dj=True)
    # sym_on is a traced 0/1 gate (RuntimeKnobs): the marking math is always
    # in the program and selected at runtime, so one compile serves both the
    # baseline and the Symphony points of a knob grid.
    p_sym = marking_probability(
        inst.iwire[:, None], inst.ipsn[:, None], sm, pw, al, cfg.sym)
    p_sym = jnp.where(inst.idom < D, p_sym, 0.0)
    sym_gate = (jnp.asarray(cfg.sym_on) != 0) & (tick >= cfg.sym_start_tick)
    p_sym = jnp.where(sym_gate, p_sym, 0.0)
    p_hop = 1.0 - (1.0 - inst.on_path(p_red)) * (1.0 - p_sym)
    log_nomark = jnp.sum(jnp.log1p(-jnp.minimum(p_hop, 0.999999)), axis=1)
    p_inst = 1.0 - jnp.exp(log_nomark)
    pkts = eff * cfg.dt / cfg.mtu
    lam = (lam.reshape(ctx.FW) +
           jnp.where(inst.active, p_inst * pkts, 0.0)).reshape(ctx.F, ctx.W)
    return lam, pkts, sm


# ------------------------------------------------------------ 6. progress
@_scoped("netsim.progress")
def stage_progress(ctx: EngineCtx, cfg, state: EngineState, inst: InstView,
                   step_of, eff, tick):
    """Advance per-instance bytes, retire completed steps in order, record
    per-slot finish ticks."""
    wl, fidx = ctx.wl, ctx.fidx
    isent_new = inst.isent + eff * cfg.dt
    newly_done = inst.active & (isent_new >= inst.ichunk)
    sent = isent_new.reshape(ctx.F, ctx.W)
    done_upto = state.done_upto
    for _ in range(2):  # <=2 completions per slot per tick in practice
        wsel = done_upto % ctx.W
        ch = ctx.chunk_of(
            wl.job, seg_global(done_upto, wl.sps, wl.phase, ctx.nph_f))
        ok = (step_of[fidx, wsel] == done_upto) & (sent[fidx, wsel] >= ch)
        done_upto = done_upto + ok.astype(jnp.int32)
    finish = jnp.where((done_upto >= wl.total_steps) &
                       (state.finish == I32MAX), tick, state.finish)
    return sent, done_upto, finish, newly_done


# ------------------------------------------------------ 7. Symphony state
@_scoped("netsim.symphony")
def stage_symphony(ctx: EngineCtx, cfg, state: EngineState, inst: InstView,
                   sm, pkts, newly_done, eff, tick):
    """Per-(domain, job) state blocks: traffic stats, optimistic step-min
    advancement with lazy correction, windowed alpha update (Alg. 1)."""
    H, DJ = ctx.H, (ctx.D + 1) * ctx.J
    # one scatter entry per (instance, hop); hops in the null domain D
    # land on rows >= D*J and are ignored by marking.
    act4 = jnp.repeat(inst.active, H)
    send4 = jnp.repeat(inst.active & (eff > 1.0), H)
    done4 = jnp.repeat(newly_done, H)
    wire4 = jnp.repeat(inst.iwire, H)
    psn4 = jnp.repeat(inst.ipsn + pkts, H)
    pkts4 = jnp.repeat(pkts, H)
    sm4 = sm.reshape(-1)
    djf = inst.djf

    cnt = state.s_cnt.at[djf].add(jnp.where(act4, pkts4, 0.0))
    cntop = state.s_cntop.at[djf].add(
        jnp.where(act4 & (wire4 > sm4), pkts4, 0.0))
    # optimistic advancement on LAST events, then lazy correction
    cand = jnp.zeros(DJ, jnp.int32).at[djf].max(
        jnp.where(done4, wire4 + 1, 0))
    cand = jnp.maximum(state.s_stepmin, cand)
    min_act = jnp.full(DJ, BIG).at[djf].min(
        jnp.where(act4 & ~done4, wire4, BIG))
    stepmin = jnp.where(min_act < BIG, jnp.minimum(cand, min_act), cand)
    psnwin = state.s_psnwin.at[djf].max(
        jnp.where(send4 & ~done4 &
                  (wire4 == inst.on_path(stepmin, dj=True).reshape(-1)),
                  psn4, 0.0))

    sym_epoch = (tick % cfg.sym_win_ticks) == (cfg.sym_win_ticks - 1)
    have = cnt > jnp.asarray(cfg.sym.n_sample, jnp.float32)
    exceed = cntop >= jnp.asarray(cfg.sym.tau, jnp.float32) * cnt
    alpha_new = jnp.clip(state.s_alpha + jnp.where(exceed, 1.0, -1.0) * have,
                         1.0, jnp.asarray(cfg.sym.alpha_max, jnp.float32))
    s_alpha = jnp.where(sym_epoch, alpha_new, state.s_alpha)
    s_cnt = jnp.where(sym_epoch, 0.0, cnt)
    s_cntop = jnp.where(sym_epoch, 0.0, cntop)
    s_psnwin = jnp.where(sym_epoch, 0.0, psnwin)
    return stepmin, s_psnwin, s_alpha, s_cnt, s_cntop


# -------------------------------------------------------- 8. rate control
@_scoped("netsim.rate_control")
def stage_rate_control(ctx: EngineCtx, cfg, starts: Starts, lam, key, tick):
    """DCQCN-style epoch update driven by the accumulated mark probability."""
    F, W = ctx.F, ctx.W
    line_rate = ctx.line_rate
    step_of = starts.step_of
    cc_epoch = (tick % cfg.cc_epoch_ticks) == (cfg.cc_epoch_ticks - 1)

    def cc_update(args):
        rate, target, alpha_cc, stage, lam, key = args
        key, sub = jax.random.split(key)
        u = jax.random.uniform(sub, (F, W))
        cut = (u < 1.0 - jnp.exp(-lam)) & (step_of >= 0)
        r_c = jnp.maximum(rate * (1.0 - alpha_cc / 2.0), cfg.cc_min_rate)
        # DCQCN: the recovery target snapshots the current rate on the
        # *first* cut of a congestion event only; consecutive cuts
        # (stage==0) keep the previous target so fast recovery can bounce
        # back to the pre-congestion operating point.
        t_c = jnp.where(stage > 0, rate, target)
        a_c = (1.0 - cfg.cc_g) * alpha_cc + cfg.cc_g
        a_n = (1.0 - cfg.cc_g) * alpha_cc
        stage_n = stage + 1
        tgt_inc = jnp.where(stage_n > cfg.cc_fr_stages,
                            jnp.where(stage_n > 2 * cfg.cc_fr_stages,
                                      cfg.cc_rhai, cfg.cc_rai), 0.0)
        t_n = jnp.minimum(target + tgt_inc, line_rate[:, None])
        r_n = jnp.minimum((rate + t_n) / 2.0, line_rate[:, None])
        return (jnp.where(cut, r_c, r_n), jnp.where(cut, t_c, t_n),
                jnp.where(cut, a_c, a_n), jnp.where(cut, 0, stage_n),
                jnp.zeros_like(lam), key)

    return jax.lax.cond(
        cc_epoch, cc_update, lambda a: a,
        (starts.rate, starts.target, starts.alpha_cc, starts.stage, lam, key))


# ----------------------------------------------------- 9. segments / jobs
@_scoped("netsim.segments")
def stage_segments(ctx: EngineCtx, state: EngineState, done_upto, tick):
    """Advance the job-wide segment barrier and record job finish ticks."""
    wl, J = ctx.wl, ctx.J
    seg_phase = state.seg_idx % wl.n_phases
    participating = wl.phase == seg_phase[wl.job]
    c_end = (state.seg_idx[wl.job] // ctx.nph_f + 1) * wl.sps
    flow_done = ((~participating) | (done_upto >= c_end)).astype(jnp.int32)
    seg_done = jnp.ones(J, jnp.int32).at[wl.job].min(flow_done) > 0
    adv = seg_done & (state.seg_idx < wl.n_segs) & (tick >= state.seg_ready)
    seg_idx = state.seg_idx + adv.astype(jnp.int32)
    new_phase0 = (seg_idx % wl.n_phases) == 0
    seg_ready = jnp.where(adv,
                          tick + jnp.where(new_phase0, wl.gap_ticks, 0),
                          state.seg_ready)
    job_finish = jnp.where((seg_idx >= wl.n_segs) &
                           (state.job_finish == I32MAX),
                           tick, state.job_finish)
    # Dependency-triggered arrivals: a pending job (seg_ready still at the
    # I32MAX sentinel) is released once its trigger job's segment barrier
    # has advanced past the required count.  Integer-only, so untriggered
    # workloads (trig_job == -1 everywhere) stay bit-for-bit unchanged.
    trig_src = jnp.clip(wl.trig_job, 0, J - 1)
    fired = (wl.trig_job >= 0) & (state.seg_ready == I32MAX) & \
            (seg_idx[trig_src] >= wl.trig_seg)
    seg_ready = jnp.where(fired,
                          tick + wl.trig_delay_ticks + wl.gap_ticks,
                          seg_ready)
    return seg_idx, seg_ready, job_finish


# ------------------------------------------------------------ 10. metrics
@_scoped("netsim.metrics")
def stage_metrics(ctx: EngineCtx, inst: InstView, done_upto, eff, q, s_alpha):
    """The sampled observables of one tick."""
    J, L = ctx.J, ctx.L
    min_wire = jnp.full(J, BIG).at[ctx.inst_job].min(
        jnp.where(inst.active, inst.iwire, BIG))
    max_wire = jnp.full(J, -1).at[ctx.inst_job].max(
        jnp.where(inst.active, inst.iwire, -1))
    done_min = jnp.full(J, BIG).at[ctx.wl.job].min(done_upto)
    # masked sum, not scatter-add: vmap batching rewrites scatter-add
    # accumulation order (ULP drift), while a fixed-axis reduction keeps
    # grid slices bitwise-equal to single runs.  J is small, so the dense
    # [J, FW] mask is cheap.
    tput = jnp.sum(
        jnp.where(ctx.inst_job[None, :] == jnp.arange(J)[:, None],
                  eff[None, :], 0.0), axis=1)
    return (min_wire, max_wire, done_min, tput, q[:L].max(), s_alpha.max())


# ------------------------------------------------------------ composition
def static_pq_on(cfg):
    """``pq_on`` as a Python bool when static, else None (traced gate)."""
    pq = getattr(cfg, "pq_on", False)
    if isinstance(pq, jax.Array):
        return None
    return bool(pq)


def resolve_share_policy(cfg) -> Callable[..., ShareResult]:
    pq = static_pq_on(cfg)
    if pq and cfg.share_policy not in ("proportional", "pq"):
        raise ValueError(
            f"pq_on=True conflicts with share_policy={cfg.share_policy!r}; "
            "drop the legacy pq_on flag when selecting a policy explicitly")
    name = "pq" if pq else cfg.share_policy
    try:
        return SHARE_POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown share policy {name!r}; have {sorted(SHARE_POLICIES)}")


@_scoped("netsim.share")
def stage_share(ctx: EngineCtx, cfg, inst: InstView, tick) -> ShareResult:
    """Bandwidth sharing with the runtime ``pq_on`` override.

    The base policy is static (``cfg.share_policy`` names the compiled
    program).  A traced ``pq_on`` gate switches to strict priority via
    ``lax.cond``: a scalar predicate executes one branch at runtime; under
    vmap (knob grids) it lowers to a select so mixed baseline/PQ grids
    still share one compilation.
    """
    base_fn = resolve_share_policy(cfg)
    if static_pq_on(cfg) is not None:   # legacy all-static config
        return base_fn(ctx, cfg, inst, tick)
    if base_fn is share_pq:
        return share_pq(ctx, cfg, inst, tick)
    return jax.lax.cond(
        jnp.asarray(cfg.pq_on) != 0,
        lambda: share_pq(ctx, cfg, inst, tick),
        lambda: base_fn(ctx, cfg, inst, tick))


BACKENDS = ("xla", "pallas")
_FALLBACK_WARNED: set = set()


def resolve_backend(cfg) -> str:
    """The tick backend actually used for this config.

    ``backend="pallas"`` fuses route-gather / bandwidth-share / queue-RED /
    Symphony-scatter into the ``kernels/netsim_tick`` Pallas kernel.  The
    kernel implements the ``proportional`` and ``pq`` share paths (plus the
    traced ``pq_on`` gate); ``wfq``/``drr`` fall back to the staged XLA
    path behind this same dispatch, logged once per policy via
    ``warnings.warn``.
    """
    be = getattr(cfg, "backend", "xla")
    if be not in BACKENDS:
        raise ValueError(f"unknown tick backend {be!r}; have {BACKENDS}")
    if be == "pallas" and cfg.share_policy not in ("proportional", "pq"):
        if cfg.share_policy not in _FALLBACK_WARNED:
            _FALLBACK_WARNED.add(cfg.share_policy)
            warnings.warn(
                f"backend='pallas' with share_policy={cfg.share_policy!r} "
                "falls back to the staged XLA tick: the fused kernel only "
                "implements the proportional/pq share paths",
                stacklevel=2)
        return "xla"
    return be


def engine_tick(ctx: EngineCtx, cfg, state: EngineState, tick):
    """One tick: compose the stages.  Returns (state', metric sample).

    Dispatches on ``cfg.backend`` (static, from :class:`SimStructure`):
    ``"xla"`` runs the staged composition below; ``"pallas"`` routes the
    hot stages through the fused ``kernels/netsim_tick`` kernel and keeps
    this composition as its golden reference.
    """
    if resolve_backend(cfg) == "pallas":
        from ...kernels.netsim_tick.ops import engine_tick_fused
        return engine_tick_fused(ctx, cfg, state, tick)
    return engine_tick_xla(ctx, cfg, state, tick)


def engine_tick_xla(ctx: EngineCtx, cfg, state: EngineState, tick):
    """The pure-XLA staged tick (the reference semantics of the engine)."""
    starts = stage_starts(ctx, state, tick)
    inst = instance_view(ctx, starts, state, cfg.mtu, cfg.per_step_ecmp)
    shr = stage_share(ctx, cfg, inst, tick)
    q, p_red = stage_queues(ctx, cfg, state.q, shr.offered)
    lam, pkts, sm = stage_marking(ctx, cfg, state, inst, p_red, shr.eff,
                                  starts.lam, tick)
    sent, done_upto, finish, newly_done = stage_progress(
        ctx, cfg, state, inst, starts.step_of, shr.eff, tick)
    stepmin, s_psnwin, s_alpha, s_cnt, s_cntop = stage_symphony(
        ctx, cfg, state, inst, sm, pkts, newly_done, shr.eff, tick)
    rate, target, alpha_cc, stage, lam, key = stage_rate_control(
        ctx, cfg, starts, lam, state.key, tick)
    seg_idx, seg_ready, job_finish = stage_segments(ctx, state, done_upto,
                                                    tick)
    sample = stage_metrics(ctx, inst, done_upto, shr.eff, q, s_alpha)
    new_state = EngineState(
        next_step=starts.next_step, done_upto=done_upto, finish=finish,
        step_of=starts.step_of, sent=sent, rate=rate, target=target,
        alpha_cc=alpha_cc, stage=stage, lam=lam, q=q,
        s_stepmin=stepmin, s_psnwin=s_psnwin, s_alpha=s_alpha,
        s_cnt=s_cnt, s_cntop=s_cntop,
        seg_idx=seg_idx, seg_ready=seg_ready, job_finish=job_finish,
        key=key,
    )
    return new_state, sample
