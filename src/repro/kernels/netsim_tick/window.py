"""Multi-tick window kernel: ``tick_window`` engine ticks per pallas call.

The per-tick path (`ops.engine_tick_fused`) round-trips every piece of
engine state through HBM once per tick: the kernel reads link queues /
Symphony windows / instance slots, writes them back, and the XLA-side
cold stages read them again.  This kernel instead fuses a *window* of
``n`` consecutive ticks into ONE ``pl.pallas_call``: the full engine
state is read once, carried through an in-kernel ``lax.fori_loop``
(state lives in registers/VMEM between ticks), and written back once —
amortizing the state HBM traffic by ``1/tick_window`` (see
``benchmarks/roofline.py``).

Each loop iteration replays the *entire* engine tick — ``stage_starts``,
the fused hot stages (`kernel.hot_tick`, the same value-level body the
single-tick kernel runs), and the cold composition (`ops.compose_tick`:
marking, progress, rate control, segment barriers, metrics) — by
rebuilding the `EngineCtx` / `EngineParams` views from the kernel's
refs, so the tick semantics are *definitionally* those of the staged
engine; equivalence is pinned in tests/test_netsim_tick_kernel.py.

Outputs are the post-window `EngineState` plus the metric sample of the
window's **last** tick, matching the simulator's record-period contract
(`simulator._core_impl` samples the last tick of each record period, so
windows are aligned to divide the period).

Scope: the window kernel keeps the whole ``[FW]`` instance axis — and
the packed per-instance route/chunk/ECMP tables (`params.PackedTables`)
— VMEM-resident across the in-kernel ``fori_loop``, so table reads cost
their one initial DMA per *window*, not per tick.  With ``blk`` set the
tiling normalizes away here (``params.plan_tiling`` returns ``None``
for ``tick_window > 1``): windowing already amortizes the state traffic
the tiling would stream.  The kernel runs in interpret mode only: the
cold stages it replays gather, scatter and draw ``jax.random`` numbers
in-kernel, none of which Mosaic lowers, so `ops.engine_window_fused`
raises a ``ValueError`` when it would be compiled.

The carried engine state is donated: the pallas call aliases each of
the ``N_STATE`` state inputs to its same-shaped state output
(``input_output_aliases``), so a record period of windows updates the
state buffers in place instead of copying them once per window.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.netsim.params import (PackedTables, RuntimeKnobs, SimStructure,
                                   SymphonyParams, merge_params,
                                   pack_route_tables)
from ...core.netsim.stages import EngineState, WLArrays, make_ctx, stage_starts
from .kernel import hot_tick

N_STATE = len(EngineState._fields)   # 20
N_WL = len(WLArrays._fields)         # 15
N_STATIC = 12                        # simulator.Static fields
N_TABLES = len(PackedTables._fields)  # 6 packed route-table operands
# Static fields that are scalars (marshalled as shape-(1,) operands):
_STATIC_SCALARS = (8, 9, 11)         # bg_period_ticks, bg_duty, seed


def _window_kernel(*refs, struct: SimStructure, n: int, policy: str,
                   segsum: str):
    from ...core.netsim.simulator import Static
    from .ops import compose_tick

    base = N_STATE + N_WL + N_STATIC
    ins = refs[:base + N_TABLES + 2]
    outs = refs[base + N_TABLES + 2:]

    state = EngineState(*(r[...] for r in ins[:N_STATE]))
    wl = WLArrays(*(r[...] for r in ins[N_STATE:N_STATE + N_WL]))
    sa = [r[...] for r in ins[N_STATE + N_WL:base]]
    for i in _STATIC_SCALARS:        # back to true scalars for broadcasting
        sa[i] = sa[i][0]
    st = Static(*sa)
    # packed route tables: read once, VMEM-resident across the fori_loop
    tables = PackedTables(*(r[...] for r in ins[base:base + N_TABLES]))
    ki = ins[base + N_TABLES]
    kf = ins[base + N_TABLES + 1]

    base_tick = ki[0]
    knobs = RuntimeKnobs(
        red_kmin=kf[0], red_kmax=kf[1], red_pmax=kf[2],
        cc_epoch_ticks=ki[1], cc_g=kf[3], cc_rai=kf[4], cc_rhai=kf[5],
        cc_fr_stages=ki[2], cc_min_rate=kf[6],
        sym_on=ki[3],
        sym=SymphonyParams(k=kf[7], tau=kf[8], n_warmup=kf[9],
                           n_sample=kf[10], alpha_max=kf[11]),
        sym_win_ticks=ki[4], sym_start_tick=ki[5], pq_on=ki[6])
    cfg = merge_params(struct, knobs)
    ctx = make_ctx(st, wl, struct.window, tables=tables)
    SEG = int(wl.chunk_sched.shape[1])
    J = ctx.J
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    i32 = lambda v: jnp.asarray(v, jnp.int32)

    def one_tick(state, tick):
        starts = stage_starts(ctx, state, tick)
        out = hot_tick(
            starts.step_of.reshape(ctx.FW), starts.sent.reshape(ctx.FW),
            starts.rate.reshape(ctx.FW), state.done_upto, state.q,
            state.s_stepmin, state.s_psnwin, state.s_alpha,
            state.s_cnt, state.s_cntop,
            st.routes, st.path_table, st.n_paths, st.cap, st.link_dom,
            st.bg_base, st.bg_amp,
            ctx.inst_job, ctx.inst_flow, ctx.sps_i, ctx.phase_i, ctx.nph_i,
            ctx.off_i, wl.chunk_sched,
            i32(tick), i32(st.seed), i32(st.bg_period_ticks),
            i32(cfg.sym_win_ticks), i32(cfg.pq_on),
            f32(st.bg_duty), f32(cfg.red_kmin), f32(cfg.red_kmax),
            f32(cfg.red_pmax), f32(cfg.sym.tau), f32(cfg.sym.n_sample),
            f32(cfg.sym.alpha_max),
            H=ctx.H, SEG=SEG, dt=cfg.dt, mtu=cfg.mtu,
            per_step_ecmp=cfg.per_step_ecmp, policy=policy, segsum=segsum,
            tables=ctx.tables)
        return compose_tick(ctx, cfg, state, tick, starts, out)

    zero_sample = (jnp.zeros(J, jnp.int32), jnp.zeros(J, jnp.int32),
                   jnp.zeros(J, jnp.int32), jnp.zeros(J, jnp.float32),
                   jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))

    def body(t, carry):
        state, _ = carry
        return one_tick(state, base_tick + t)

    state, sample = jax.lax.fori_loop(0, n, body, (state, zero_sample))

    for r, v in zip(outs[:N_STATE], state):
        r[...] = v
    minw, maxw, dmin, tput, qmax, amax = sample
    outs[N_STATE][...] = minw
    outs[N_STATE + 1][...] = maxw
    outs[N_STATE + 2][...] = dmin
    outs[N_STATE + 3][...] = tput
    outs[N_STATE + 4][0] = qmax
    outs[N_STATE + 5][0] = amax


def netsim_window(ctx, cfg, state: EngineState, base_tick, n: int, *,
                  policy: str, segsum: str, interpret: bool):
    """Dispatch ``n`` ticks starting at ``base_tick`` as one kernel call.

    Returns ``(state after n ticks, metric sample of tick base_tick+n-1)``
    with the exact `stages.engine_tick` sample/state contract.
    """
    st, wl = ctx.st, ctx.wl
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    struct = SimStructure(
        dt=cfg.dt, n_ticks=cfg.n_ticks, window=cfg.window, mtu=cfg.mtu,
        record_every=cfg.record_every, share_policy=cfg.share_policy,
        deploy=cfg.deploy, per_step_ecmp=cfg.per_step_ecmp,
        backend=cfg.backend, segsum=cfg.segsum, blk=cfg.blk,
        tick_window=cfg.tick_window)
    ki = jnp.stack([i32(base_tick), i32(cfg.cc_epoch_ticks),
                    i32(cfg.cc_fr_stages), i32(cfg.sym_on),
                    i32(cfg.sym_win_ticks), i32(cfg.sym_start_tick),
                    i32(cfg.pq_on)])
    kf = jnp.stack([f32(cfg.red_kmin), f32(cfg.red_kmax), f32(cfg.red_pmax),
                    f32(cfg.cc_g), f32(cfg.cc_rai), f32(cfg.cc_rhai),
                    f32(cfg.cc_min_rate), f32(cfg.sym.k), f32(cfg.sym.tau),
                    f32(cfg.sym.n_warmup), f32(cfg.sym.n_sample),
                    f32(cfg.sym.alpha_max)])
    sa = list(st)
    for i in _STATIC_SCALARS:
        sa[i] = sa[i].reshape(1)
    tables = ctx.tables if getattr(ctx, "tables", None) is not None \
        else pack_route_tables(st, wl, cfg.window)
    operands = list(state) + list(wl) + sa + list(tables) + [ki, kf]

    J = ctx.J
    out_shape = ([jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state]
                 + [jax.ShapeDtypeStruct((J,), jnp.int32)] * 3
                 + [jax.ShapeDtypeStruct((J,), jnp.float32),
                    jax.ShapeDtypeStruct((1,), jnp.float32),
                    jax.ShapeDtypeStruct((1,), jnp.float32)])
    outs = pl.pallas_call(
        partial(_window_kernel, struct=struct, n=int(n), policy=policy,
                segsum=segsum),
        out_shape=out_shape,
        # state operand i writes state output i (same shape/dtype): donate
        # the carried buffers so chained windows update state in place
        # instead of copying all N_STATE arrays once per window.
        input_output_aliases={i: i for i in range(N_STATE)},
        interpret=interpret,
        name="netsim_window",
    )(*operands)
    new_state = EngineState(*outs[:N_STATE])
    sample = (outs[N_STATE], outs[N_STATE + 1], outs[N_STATE + 2],
              outs[N_STATE + 3], outs[N_STATE + 4][0], outs[N_STATE + 5][0])
    return new_state, sample
