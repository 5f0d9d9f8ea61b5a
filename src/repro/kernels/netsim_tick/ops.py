"""Engine-facing entry points for the fused netsim tick kernel.

`stages.engine_tick` dispatches here when ``cfg.backend == "pallas"``:
:func:`engine_tick_fused` runs the hot stages (instance view, route
selection, bandwidth sharing, queue/RED, Symphony scatter) inside the
Pallas kernel and composes the remaining cheap stages (marking, progress,
rate control, segment barriers, metrics) around it on the XLA side —
bit-for-bit equal to `stages.engine_tick_xla` in interpret mode.

Interpret or compiled: :func:`use_interpret` decides (interpret on a
CPU backend, compiled elsewhere; ``REPRO_PALLAS_INTERPRET=0|1``
overrides).  Interpreted, the kernel traces into the same XLA program as
the staged engine, so it is a correctness path.  Compiled for a TPU only
the tiled one-hot kernel lowers (``segsum="onehot"``, ``blk`` a multiple
of 128 below ``F*W``, ``tick_window=1``); the other variants gather,
scatter or draw random numbers inside the kernel, which Mosaic cannot
lower, and raise a ``ValueError`` here before tracing reaches Mosaic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.netsim.params import (PackedTables, pack_route_tables,
                                   plan_tiling)
from ...core.netsim.stages import (EngineState, instance_view, stage_marking,
                                   stage_metrics, stage_progress,
                                   stage_rate_control, stage_segments,
                                   stage_starts, static_pq_on)
from .. import use_interpret
from .kernel import TickOut, netsim_tick

__all__ = ["use_interpret", "kernel_policy", "plan_tiling", "PackedTables",
           "pack_route_tables", "fused_tick", "compose_tick",
           "engine_tick_fused", "engine_window_fused"]


def kernel_policy(cfg) -> str:
    """The in-kernel share policy for this config ("proportional"|"pq")."""
    if cfg.share_policy == "pq" or static_pq_on(cfg):
        return "pq"
    return "proportional"


def _check_lowerable(FW: int, segsum: str, blk: int | None) -> None:
    """Raise unless the compiled (Mosaic) kernel can lower this variant:
    only the tiled one-hot kernel has a TPU lowering."""
    if segsum != "onehot":
        raise ValueError(
            f"segsum={segsum!r} has no compiled TPU lowering (in-kernel "
            "vector scatters and gathers); use segsum='onehot' with "
            "blk=256, or run in interpret mode")
    if blk is None:
        raise ValueError(
            f"the untiled segsum='onehot' kernel (blk unset or >= F*W={FW}) "
            "has no compiled TPU lowering (whole-axis gathers); set "
            "blk=256 (a multiple of 128 below F*W)")
    if blk % 128:
        raise ValueError(
            f"blk={blk} must be a multiple of 128 (the TPU lane tile) for "
            "the compiled tiled kernel; use blk=256")


def fused_tick(ctx, cfg, starts, state, tick, *,
               segsum: str | None = None,
               blk: int | None = None,
               interpret: bool | None = None) -> TickOut:
    """Marshal engine state into the kernel's flat operands and run it.

    ``segsum`` / ``blk`` default to the config's static fields (both
    overridable for direct kernel tests).  The XLA ops around the kernel
    (scalar packing, the tiled kernel's operand layout, the slicing of
    its outputs) run under the named scope ``netsim.kernel_operands``."""
    st = ctx.st
    if segsum is None:
        segsum = getattr(cfg, "segsum", "scatter")
    if blk is None:
        blk = getattr(cfg, "blk", None)
    blk = plan_tiling(ctx.FW, blk, segsum, getattr(cfg, "tick_window", 1))
    if interpret is None:
        interpret = use_interpret()
    if not interpret:
        _check_lowerable(ctx.FW, segsum, blk)
    with jax.named_scope("netsim.kernel_operands"):
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        iscal = jnp.stack([i32(tick), i32(st.seed), i32(st.bg_period_ticks),
                           i32(cfg.sym_win_ticks), i32(cfg.pq_on)])
        fscal = jnp.stack([f32(st.bg_duty), f32(cfg.red_kmin),
                           f32(cfg.red_kmax), f32(cfg.red_pmax),
                           f32(cfg.sym.tau), f32(cfg.sym.n_sample),
                           f32(cfg.sym.alpha_max)])
        return netsim_tick(
            starts.step_of.reshape(ctx.FW), starts.sent.reshape(ctx.FW),
            starts.rate.reshape(ctx.FW), state.done_upto, state.q,
            state.s_stepmin, state.s_psnwin, state.s_alpha,
            state.s_cnt, state.s_cntop,
            st.routes, st.path_table, st.n_paths, st.cap, st.link_dom,
            st.bg_base, st.bg_amp,
            ctx.inst_job, ctx.inst_flow, ctx.sps_i, ctx.phase_i, ctx.nph_i,
            ctx.off_i, ctx.wl.chunk_sched, iscal, fscal,
            dt=cfg.dt, mtu=cfg.mtu, per_step_ecmp=cfg.per_step_ecmp,
            policy=kernel_policy(cfg), segsum=segsum, blk=blk,
            tables=getattr(ctx, "tables", None),
            interpret=interpret)


def compose_tick(ctx, cfg, state: EngineState, tick, starts, out: TickOut):
    """Compose the cheap stages around the fused hot-path outputs into the
    engine-tick contract ``(state', metric sample)``.  Shared between the
    per-tick path (XLA-side, around the pallas call) and the multi-tick
    window kernel (replayed inside the kernel body per tick).  The routes
    come from :func:`instance_view`'s pick on the same ECMP hash the kernel
    takes, so ``out.iroute`` is not read here."""
    inst = instance_view(ctx, starts, state, cfg.mtu, cfg.per_step_ecmp)
    lam, _pkts, _sm = stage_marking(ctx, cfg, state, inst, out.p_red,
                                    out.eff, starts.lam, tick)
    sent, done_upto, finish, _newly_done = stage_progress(
        ctx, cfg, state, inst, starts.step_of, out.eff, tick)
    rate, target, alpha_cc, stage, lam, key = stage_rate_control(
        ctx, cfg, starts, lam, state.key, tick)
    seg_idx, seg_ready, job_finish = stage_segments(ctx, state, done_upto,
                                                    tick)
    sample = stage_metrics(ctx, inst, done_upto, out.eff, out.q, out.s_alpha)
    new_state = EngineState(
        next_step=starts.next_step, done_upto=done_upto, finish=finish,
        step_of=starts.step_of, sent=sent, rate=rate, target=target,
        alpha_cc=alpha_cc, stage=stage, lam=lam, q=out.q,
        s_stepmin=out.s_stepmin, s_psnwin=out.s_psnwin, s_alpha=out.s_alpha,
        s_cnt=out.s_cnt, s_cntop=out.s_cntop,
        seg_idx=seg_idx, seg_ready=seg_ready, job_finish=job_finish,
        key=key,
    )
    return new_state, sample


def engine_tick_fused(ctx, cfg, state: EngineState, tick):
    """One tick with the hot stages fused; same contract as
    `stages.engine_tick_xla`: returns ``(state', metric sample)``."""
    starts = stage_starts(ctx, state, tick)
    out = fused_tick(ctx, cfg, starts, state, tick)
    return compose_tick(ctx, cfg, state, tick, starts, out)


def engine_window_fused(ctx, cfg, state: EngineState, base_tick, n: int):
    """Run ``n`` consecutive ticks inside ONE kernel invocation.

    The whole tick — start gating, the fused hot stages, marking,
    progress, rate control, segments, metrics — executes inside the
    Pallas kernel with the engine state carried through an in-kernel
    ``fori_loop``, so link/Symphony/instance state round-trips HBM once
    per window instead of once per tick.  Returns ``(state after n
    ticks, metric sample of the last tick)``.
    """
    from .window import netsim_window
    plan_tiling(ctx.FW, getattr(cfg, "blk", None),
                getattr(cfg, "segsum", "scatter"),
                getattr(cfg, "tick_window", 1))
    interpret = use_interpret()
    if not interpret:
        raise ValueError(
            f"tick_window={cfg.tick_window} runs the multi-tick window "
            "kernel, which has no compiled TPU lowering (it replays the "
            "cold stages' gathers, scatters and jax.random in-kernel); use "
            "tick_window=1 with segsum='onehot', blk=256")
    return netsim_window(ctx, cfg, state, base_tick, n,
                         policy=kernel_policy(cfg),
                         segsum=getattr(cfg, "segsum", "scatter"),
                         interpret=interpret)
