"""Fused netsim tick hot path as a Pallas kernel.

The staged XLA engine (`core/netsim/stages.py`) runs each hot stage of a
tick — route gather, per-link scatter-add bandwidth sharing, queue/RED
integration, Symphony per-(domain, job) scatter — as a separate XLA op
with its own HBM round trip.  This kernel fuses them into one program:
the per-instance view, both share classes, the link queues, and the
Symphony state block updates are computed with everything resident
on-chip, and only the tick's true inputs/outputs touch HBM.

The stage functions stay the golden reference (`ref.py`): the kernel body
replays their op sequence exactly, so in interpret mode the fused tick is
**bit-for-bit** identical to the staged engine — the golden chain
(Table-1 finish-tick traces) holds under ``backend="pallas"``.

Share policies: ``proportional`` and ``pq`` are implemented in-kernel
(both classes are computed and the traced ``pq_on`` gate selects, exactly
like the XLA path's ``lax.cond``-under-vmap select); ``wfq``/``drr`` stay
on the XLA path behind `stages.resolve_backend`.

Segment reductions come in two flavors (``segsum=``):

* ``"scatter"`` — `.at[].add/max/min`, the reference op sequence;
  bitwise-equal to the staged engine (interpret mode).
* ``"onehot"``  — dense one-hot masks reduced along the instance axis
  (the monolithic kernel contracts the adds with a matmul; the tiled
  kernel sums masked rows on the vector unit, exact f32 adds).  Mosaic
  has no vector scatter, so this is the shape a compiled TPU lowering
  takes; adds reassociate, so it is allclose-not-bitwise vs the
  reference.

Tiling (``blk=``): the onehot variant additionally runs as a proper grid
kernel over the flat ``[FW]`` instance axis — ``grid = (4 sweeps,
ceil(FW/blk) blocks)`` with ``BlockSpec``-tiled per-instance operands, so
the dense one-hot contraction is ``[L+1, blk*H]`` per block instead of
``[L+1, FW*H]`` and the working set fits VMEM at any instance count.
The tick's chained global reductions (job min-wire -> link scales ->
eff -> Symphony step-min -> psn-window) force multiple passes over the
instance blocks; each pass is one sweep of the grid, with the ``[J]`` /
``[L+1]`` / ``[DJ]`` reductions accumulated as per-block partials in
persistent scratch:

  sweep 0   job min-wire partials + proportional offered-load partials
  sweep 1   hi/lo-class offered-load partials (needs complete min-wire)
  sweep 2   link scales finalized (block 0), then per-block eff +
            Symphony cnt/cntop/step-min partials
  sweep 3   step-min finalized (block 0), per-block psn-window partials,
            per-instance outputs; final block flushes link/Symphony outs

min/max reductions accumulate exactly (associative), so integer outputs
match the untiled kernel bit-for-bit; the float adds reassociate across
blocks (allclose), same contract as ``onehot`` itself.  Non-dividing
``FW`` is edge-padded to a whole number of blocks and the padded rows
masked inactive.  Under ``jax.vmap`` (the grid executor's lane batching)
the whole thing stays ONE ``pallas_call`` with a leading lane axis
prepended to the grid: ``[lanes, 4, FW_blocks]``.

Gather-free tiling: the tiled kernel takes no index-table operands at
all.  Every former gather — ``routes[inst_flow]``, the per-step ECMP
candidate lookup, ``chunk_sched[inst_job]``, ``done_upto[inst_flow]`` —
is replaced by *packed per-instance tables* (`params.pack_route_tables`)
streamed block-by-block through the same BlockSpec pipeline as the
instance state, plus iota-select-and-sum reads (`_col_take` /
`_sub_select`: exactly one selected entry per output, so the masked sum
is value-exact) for the in-kernel dynamic lookups (ECMP candidate
choice, per-link scales, Symphony rows).  Per-block valid-row counts
ride in scalar prefetch (``PrefetchScalarGridSpec``), so block shapes
stay static and the next block's table DMA overlaps compute.  The
resulting TPU-platform StableHLO contains **zero** ``stablehlo.gather``
and **zero** ``stablehlo.scatter`` ops.

Only the tiled kernel compiles for a TPU (``tests/test_tpu_compile.py``
compiles it for a described v5e at Table-1 and 512-host widths; the
layout it needs is described above :func:`_tiled_tick_kernel`).  The
monolithic kernel, in either ``segsum`` mode, gathers from whole-axis
tables and runs in interpret mode only: `ops.fused_tick` refuses it when
compiled.  `repro.kernels.use_interpret` picks interpret mode exactly on
a CPU backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.netsim.stages import WIRE_SEG, per_hop
from .. import use_interpret

# stages.BIG as a Python int: the kernel body must not capture device
# constants (pallas requires all array operands to be explicit inputs).
_BIG = 2**30

SEGSUM_MODES = ("scatter", "onehot")

# sweeps of the tiled grid (see module docstring)
TILED_SWEEPS = 4


class TickOut(NamedTuple):
    """Fused-kernel outputs: everything the XLA-side stages still need."""
    iroute: jax.Array     # [FW, H]  selected per-instance routes
    eff: jax.Array        # [FW]     delivered bytes/s per instance
    offered: jax.Array    # [L+1]    offered load per link
    q: jax.Array          # [L+1]    integrated queues
    p_red: jax.Array      # [L+1]    RED marking profile
    s_stepmin: jax.Array  # [DJ]     Symphony state block (post-update)
    s_psnwin: jax.Array
    s_alpha: jax.Array
    s_cnt: jax.Array
    s_cntop: jax.Array


# ------------------------------------------------- segment reductions
def _rows(n: int, m: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (n, m), 0)


def _segadd(base, idx, vals, mode):
    """``base.at[idx].add(vals)``; dense mode uses a one-hot contraction
    (MXU-friendly, reassociates the adds — allclose, not bitwise)."""
    if mode == "scatter":
        return base.at[idx].add(vals)
    oh = _rows(base.shape[0], idx.shape[0]) == idx[None, :]
    if jnp.issubdtype(vals.dtype, jnp.floating):
        return base + jnp.dot(oh.astype(vals.dtype), vals,
                              preferred_element_type=vals.dtype)
    return base + jnp.where(oh, vals[None, :], 0).sum(axis=1)


def _segmax(base, idx, vals, mode):
    if mode == "scatter":
        return base.at[idx].max(vals)
    neutral = (jnp.finfo(vals.dtype).min
               if jnp.issubdtype(vals.dtype, jnp.floating)
               else jnp.iinfo(vals.dtype).min)
    oh = _rows(base.shape[0], idx.shape[0]) == idx[None, :]
    return jnp.maximum(base, jnp.where(oh, vals[None, :], neutral).max(axis=1))


def _segmin(base, idx, vals, mode):
    if mode == "scatter":
        return base.at[idx].min(vals)
    neutral = (jnp.finfo(vals.dtype).max
               if jnp.issubdtype(vals.dtype, jnp.floating)
               else jnp.iinfo(vals.dtype).max)
    oh = _rows(base.shape[0], idx.shape[0]) == idx[None, :]
    return jnp.minimum(base, jnp.where(oh, vals[None, :], neutral).min(axis=1))


def _zero_null_link(q, L, mode):
    """``q.at[L].set(0.0)``: the trailing null link never queues.  The
    dense mode uses an iota select — bitwise-identical values (pure
    select, no arithmetic), but no scatter op for Mosaic to choke on."""
    if mode == "scatter":
        return q.at[L].set(0.0)
    return jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, q.shape, 0) == L, 0.0, q)


# ----------------------------------------------- gather-free table reads
def _onehot_col(table, idx):
    """Gather-free row-wise column select: ``table[arange(N), idx]`` for
    a ``[N, C]`` table and ``[N]`` indices.  Exactly one entry is
    selected per output, so the masked sum is value-exact (``x + 0 ==
    x``) — bitwise-equal to the gather for ints and for the non-negative
    floats used here."""
    oh = (jax.lax.broadcasted_iota(jnp.int32, table.shape, 1)
          == idx[:, None])
    return jnp.where(oh, table, 0).sum(axis=1)


def _onehot_plane(table, idx):
    """Gather-free ``table[arange(N), idx, :]`` for a ``[N, P, H]``
    candidate slab and ``[N]`` choices: iota-select over the middle
    axis, exactly one plane selected per row (value-exact)."""
    N, P = table.shape[0], table.shape[1]
    oh = (jax.lax.broadcasted_iota(jnp.int32, (N, P), 1)
          == idx[:, None])[:, :, None]
    return jnp.where(oh, table, 0).sum(axis=1)


# ------------------------------------------------ value-level hot stages
def hot_tick(istep, isent, irate, done_upto, q_prev,
             s_stepmin, s_psnwin, s_alpha, s_cnt, s_cntop,
             routes, path_table, n_paths, cap, link_dom, bg_base, bg_amp,
             inst_job, inst_flow, sps, phase, nph, off, chunk_sched,
             tick, seed, bg_period, sym_win, pq_on,
             bg_duty, red_kmin, red_kmax, red_pmax, tau, n_sample, alpha_max,
             *, H, SEG, dt, mtu, per_step_ecmp, policy, segsum,
             tables=None) -> TickOut:
    """The fused hot stages on plain values (the monolithic kernel body,
    also replayed per tick by the multi-tick window kernel).  Op order
    replays the stage functions exactly — bitwise in scatter mode.

    With ``tables`` (a `params.PackedTables`) the per-flow/per-job table
    gathers become per-instance row reads and iota-selects; every
    replaced read is an int or exactly-one-nonzero select, so the
    bitwise contract is unchanged.  The multi-tick window kernel passes
    tables so they stay VMEM-resident across its ``fori_loop``.
    """
    J = chunk_sched.shape[0]
    DJ = s_stepmin.shape[0]
    L = cap.shape[0] - 1

    # ---- instance view (stages.instance_view, on-chip)
    iseg = (istep // sps) * nph + phase
    if tables is None:
        ichunk = chunk_sched[inst_job, jnp.clip(iseg, 0, SEG - 1)]
        done_i = done_upto[inst_flow]
    else:
        ichunk = _onehot_col(tables.chunk, jnp.clip(iseg, 0, SEG - 1))
        done_i = jnp.repeat(done_upto, istep.shape[0] // done_upto.shape[0])
    iwire = iseg * WIRE_SEG + istep % sps + off
    occupied = istep >= 0
    retired = occupied & (istep < done_i)
    complete = occupied & (isent >= ichunk)
    active = occupied & ~complete & ~retired
    ipsn = isent / mtu

    # ---- route selection (stages.select_routes)
    if per_step_ecmp:
        h = (inst_flow.astype(jnp.uint32) * jnp.uint32(2654435761)
             + jnp.maximum(istep, 0).astype(jnp.uint32) * jnp.uint32(40503)
             + (seed.astype(jnp.uint32) + 1) * jnp.uint32(2246822519))
        h = (h ^ (h >> 13)) * jnp.uint32(2654435761)
        h = h ^ (h >> 16)
        if tables is None:
            n_p = n_paths[inst_flow].astype(jnp.uint32)
        else:
            n_p = tables.n_paths.astype(jnp.uint32)
        choice = (h % n_p).astype(jnp.int32)
        if tables is None:
            iroute = path_table[inst_flow, choice]
            idom = link_dom[iroute]
        else:
            iroute = _onehot_plane(tables.cand, choice)
            idom = _onehot_plane(tables.cand_dom, choice)
    elif tables is None:
        iroute = routes[inst_flow]
        idom = link_dom[iroute]
    else:
        iroute = tables.routes
        idom = tables.route_dom
    flat_links = iroute.reshape(-1)

    def lsum(vals):
        return _segadd(jnp.zeros(L + 1, jnp.float32), flat_links,
                       per_hop(vals, H), segsum)

    # ---- bandwidth sharing (stages.share_proportional / share_pq)
    bg_on = (tick % bg_period).astype(jnp.float32) < \
        bg_duty * bg_period.astype(jnp.float32)
    bg = bg_base + jnp.where(bg_on, bg_amp, 0.0)
    w_rate = jnp.where(active, irate, 0.0)

    off_p = lsum(w_rate) + bg
    s_l = jnp.minimum(1.0, cap / jnp.maximum(off_p, 1.0))
    eff_p = w_rate * s_l[iroute].min(axis=1)

    job_min_wire = _segmin(jnp.full(J, _BIG, jnp.int32), inst_job,
                           jnp.where(active, iwire, _BIG), segsum)
    is_hi = active & (iwire <= job_min_wire[inst_job])
    hi_rate = jnp.where(is_hi, irate, 0.0)
    off_hi = lsum(hi_rate) + bg
    s_hi = jnp.minimum(1.0, cap / jnp.maximum(off_hi, 1.0))
    rem = jnp.maximum(cap - off_hi * s_hi, 0.0)
    lo_rate = jnp.where(active & ~is_hi, irate, 0.0)
    off_lo = lsum(lo_rate)
    s_lo = rem / jnp.maximum(off_lo, 1.0)
    share = jnp.where(is_hi[:, None], s_hi[iroute],
                      jnp.minimum(1.0, s_lo[iroute]))
    eff_q = w_rate * share.min(axis=1)
    off_q = off_hi + off_lo

    if policy == "pq":
        eff, offered = eff_q, off_q
    else:
        gate = pq_on != 0
        eff = jnp.where(gate, eff_q, eff_p)
        offered = jnp.where(gate, off_q, off_p)

    # ---- queues + RED (stages.stage_queues)
    q = jnp.maximum(q_prev + (offered - cap) * dt, 0.0)
    q = _zero_null_link(q, L, segsum)
    p_red = jnp.clip((q - red_kmin) / (red_kmax - red_kmin),
                     0.0, 1.0) * red_pmax

    # ---- Symphony per-(domain, job) scatter (stages.stage_symphony)
    dj = idom * J + inst_job[:, None]
    djf = dj.reshape(-1)
    sm = s_stepmin[dj]
    pkts = eff * dt / mtu
    newly_done = active & (isent + eff * dt >= ichunk)

    act4 = per_hop(active, H)
    send4 = per_hop(active & (eff > 1.0), H)
    done4 = per_hop(newly_done, H)
    wire4 = per_hop(iwire, H)
    psn4 = per_hop(ipsn + pkts, H)
    pkts4 = per_hop(pkts, H)
    sm4 = sm.reshape(-1)

    cnt = _segadd(s_cnt, djf, jnp.where(act4, pkts4, 0.0), segsum)
    cntop = _segadd(s_cntop, djf,
                    jnp.where(act4 & (wire4 > sm4), pkts4, 0.0), segsum)
    cand = _segmax(jnp.zeros(DJ, jnp.int32), djf,
                   jnp.where(done4, wire4 + 1, 0), segsum)
    cand = jnp.maximum(s_stepmin, cand)
    min_act = _segmin(jnp.full(DJ, _BIG, jnp.int32), djf,
                      jnp.where(act4 & ~done4, wire4, _BIG), segsum)
    stepmin = jnp.where(min_act < _BIG, jnp.minimum(cand, min_act), cand)
    psnwin = _segmax(s_psnwin, djf,
                     jnp.where(send4 & ~done4 & (wire4 == stepmin[djf]),
                               psn4, 0.0), segsum)

    sym_epoch = (tick % sym_win) == (sym_win - 1)
    have = cnt > n_sample
    exceed = cntop >= tau * cnt
    alpha_new = jnp.clip(
        s_alpha + jnp.where(exceed, 1.0, -1.0) * have,
        1.0, alpha_max)

    return TickOut(
        iroute=iroute, eff=eff, offered=offered, q=q, p_red=p_red,
        s_stepmin=stepmin,
        s_psnwin=jnp.where(sym_epoch, 0.0, psnwin),
        s_alpha=jnp.where(sym_epoch, alpha_new, s_alpha),
        s_cnt=jnp.where(sym_epoch, 0.0, cnt),
        s_cntop=jnp.where(sym_epoch, 0.0, cntop))


# ------------------------------------------------- monolithic kernel body
def _tick_kernel(step_ref, sent_ref, rate_ref, done_ref, q_ref,
                 smin_ref, spsn_ref, salpha_ref, scnt_ref, scntop_ref,
                 routes_ref, table_ref, npaths_ref, cap_ref, dom_ref,
                 bgb_ref, bga_ref,
                 job_ref, flow_ref, sps_ref, phase_ref, nph_ref, off_ref,
                 chunk_ref, iscal_ref, fscal_ref,
                 iroute_o, eff_o, offered_o, q_o, pred_o,
                 smin_o, spsn_o, salpha_o, scnt_o, scntop_o,
                 *, H, SEG, dt, mtu, per_step_ecmp, policy, segsum):
    out = hot_tick(
        step_ref[...], sent_ref[...], rate_ref[...], done_ref[...],
        q_ref[...], smin_ref[...], spsn_ref[...], salpha_ref[...],
        scnt_ref[...], scntop_ref[...],
        routes_ref[...], table_ref[...], npaths_ref[...], cap_ref[...],
        dom_ref[...], bgb_ref[...], bga_ref[...],
        job_ref[...], flow_ref[...], sps_ref[...], phase_ref[...],
        nph_ref[...], off_ref[...], chunk_ref[...],
        iscal_ref[0], iscal_ref[1], iscal_ref[2], iscal_ref[3], iscal_ref[4],
        fscal_ref[0], fscal_ref[1], fscal_ref[2], fscal_ref[3], fscal_ref[4],
        fscal_ref[5], fscal_ref[6],
        H=H, SEG=SEG, dt=dt, mtu=mtu, per_step_ecmp=per_step_ecmp,
        policy=policy, segsum=segsum)
    iroute_o[...] = out.iroute
    eff_o[...] = out.eff
    offered_o[...] = out.offered
    q_o[...] = out.q
    pred_o[...] = out.p_red
    smin_o[...] = out.s_stepmin
    spsn_o[...] = out.s_psnwin
    salpha_o[...] = out.s_alpha
    scnt_o[...] = out.s_cnt
    scntop_o[...] = out.s_cntop


# ----------------------------------------------------- tiled kernel body
# Mosaic tiles the last two dims of every block by (8, 128): a block dim
# must be a multiple of the tile or the whole array dim.  The tiled
# kernel therefore lays its operands out as
#   per-instance vectors   (1, FW)   lane rows, ``(1, blk)`` blocks
#   per-instance tables    (K, FW)   transposed, ``(K, blk)`` blocks
#   link / Symphony state  (R, 1)    sublane columns, whole-array blocks
#   traced scalars         (1, n)    SMEM
# so every block stays legal when ``vmap`` prepends a squeezed lane dim.
# Segment reductions compare a row of ids against a sublane iota of the
# target rows: ``(rows, blk)`` masks, reduced along lanes for scatters
# onto a column and along sublanes for reads of a column.

# target rows per reduction step; bounds the mask (and the unrolled code)
# at any link count
ROW_CHUNK = 128


def _pad_rows(n: int) -> int:
    """Rows of a column operand: sublane-aligned, whole ``ROW_CHUNK``s."""
    if n <= ROW_CHUNK:
        return -(-n // 8) * 8
    return -(-n // ROW_CHUNK) * ROW_CHUNK


def _row_loop(n_rows, body, init):
    """Run ``body(r0, lc, carry)`` over the ``lc``-row chunks of ``n_rows``."""
    lc = min(n_rows, ROW_CHUNK)
    n = n_rows // lc
    if n == 1:
        return body(0, lc, init)
    return jax.lax.fori_loop(
        0, n, lambda c, carry: body(pl.multiple_of(c * lc, lc), lc, carry),
        init)


def _col_take(cols, idx):
    """``[col[idx] for col in cols]`` for ``(R, 1)`` column refs and a
    ``(1, blk)`` row of row ids.  Exactly one row matches each id, so the
    masked sum is value-exact."""
    blk = idx.shape[1]

    def body(r0, lc, accs):
        hit = (r0 + jax.lax.broadcasted_iota(jnp.int32, (lc, blk), 0)) == idx
        return tuple(
            a + jnp.sum(jnp.where(hit, c[pl.ds(r0, lc), :], 0), axis=0,
                        keepdims=True)
            for a, c in zip(accs, cols))

    return _row_loop(cols[0].shape[0], body,
                     tuple(jnp.zeros((1, blk), c.dtype) for c in cols))


_REDUCE = {"add": (jnp.sum, jnp.add), "max": (jnp.max, jnp.maximum),
           "min": (jnp.min, jnp.minimum)}


def _neutral(op, dtype):
    if op == "add":
        return 0
    info = (jnp.finfo if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo)(
        dtype)
    return info.min if op == "max" else info.max


def _col_scatter(acc, op, pairs):
    """``acc[idx] op= vals`` for every ``(idx, vals)`` pair of ``(1, blk)``
    rows, accumulated into the ``(R, 1)`` column ref ``acc``."""
    red, comb = _REDUCE[op]
    neutral = _neutral(op, acc.dtype)
    blk = pairs[0][0].shape[1]

    def body(r0, lc, carry):
        ids = r0 + jax.lax.broadcasted_iota(jnp.int32, (lc, blk), 0)
        part = acc[pl.ds(r0, lc), :]
        for idx, vals in pairs:
            part = comb(part, red(jnp.where(ids == idx, vals, neutral),
                                  axis=1, keepdims=True))
        acc[pl.ds(r0, lc), :] = part
        return carry

    _row_loop(acc.shape[0], body, 0)


def _sub_select(slab, choice):
    """Row ``choice[i]`` of a ``(P, blk)`` slab, per lane ``i``."""
    hit = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0) == choice
    return jnp.sum(jnp.where(hit, slab, 0), axis=0, keepdims=True)


def _tiled_tick_kernel(*refs, H, P, J, SEG, L, blk, dt, mtu, per_step_ecmp,
                       policy):
    """One tick, tiled over the instance axis: grid = (sweep, block).

    Gather-free: per-instance refs — including the packed route/chunk/
    ECMP tables — hold one ``blk``-lane block (BlockSpec-sliced); link/
    Symphony refs hold whole columns; there are no index-table operands
    left to gather from.  ``refs[0]`` is the scalar-prefetch ref with
    the per-block valid counts (the only trace-time metadata the blocks
    need — keeping it lane-invariant is what lets ``vmap`` batch the
    lane axis into this one ``pallas_call``).  The scratch refs persist
    across grid steps and carry the cross-block partials.
    """
    nroute = 3 if per_step_ecmp else 2
    n_in = 20 + nroute + 2
    nvalid_ref = refs[0]
    ins = refs[1:1 + n_in]
    outs = refs[1 + n_in:1 + n_in + 10]
    (jobmin_s, offp_s, offhi_s, offlo_s, sl_s, shi_s, slo_s,
     cnt_s, cntop_s, cand_s, minact_s, stepmin_s, psnwin_s) = \
        refs[1 + n_in + 10:]

    (step_ref, sent_ref, rate_ref, done_ref,
     q_ref, smin_ref, spsn_ref, salpha_ref, scnt_ref, scntop_ref,
     cap_ref, bgb_ref, bga_ref,
     job_ref, flow_ref, sps_ref, phase_ref, nph_ref, off_ref,
     chunk_ref) = ins[:20]
    route_refs = ins[20:20 + nroute]
    iscal_ref, fscal_ref = ins[20 + nroute:]
    (iroute_o, eff_o, offered_o, q_o, pred_o,
     smin_o, spsn_o, salpha_o, scnt_o, scntop_o) = outs

    s = pl.program_id(0)
    b = pl.program_id(1)
    nb = pl.num_programs(1)

    istep = step_ref[...]
    isent = sent_ref[...]
    irate = rate_ref[...]
    inst_job = job_ref[...]
    sps = sps_ref[...]
    tick, seed = iscal_ref[0, 0], iscal_ref[0, 1]
    bg_period, sym_win, pq_on = iscal_ref[0, 2], iscal_ref[0, 3], \
        iscal_ref[0, 4]
    bg_duty = fscal_ref[0, 0]
    red_kmin, red_kmax, red_pmax = fscal_ref[0, 1], fscal_ref[0, 2], \
        fscal_ref[0, 3]
    tau, n_sample, alpha_max = fscal_ref[0, 4], fscal_ref[0, 5], \
        fscal_ref[0, 6]

    # ---- per-block instance view; edge-padded lanes are masked inactive
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) < nvalid_ref[b]
    iseg = (istep // sps) * nph_ref[...] + phase_ref[...]
    ichunk = _sub_select(chunk_ref[...], jnp.clip(iseg, 0, SEG - 1))
    iwire = iseg * WIRE_SEG + istep % sps + off_ref[...]
    occupied = istep >= 0
    retired = occupied & (istep < done_ref[...])
    complete = occupied & (isent >= ichunk)
    active = occupied & ~complete & ~retired & valid
    ipsn = isent / mtu

    if per_step_ecmp:
        cand_ref, cdom_ref, npaths_ref = route_refs
        h = (flow_ref[...].astype(jnp.uint32) * jnp.uint32(2654435761)
             + jnp.maximum(istep, 0).astype(jnp.uint32) * jnp.uint32(40503)
             + (seed.astype(jnp.uint32) + 1) * jnp.uint32(2246822519))
        h = (h ^ (h >> 13)) * jnp.uint32(2654435761)
        h = h ^ (h >> 16)
        choice = (h % npaths_ref[...].astype(jnp.uint32)).astype(jnp.int32)
        # candidate slabs hold hop k of every path in rows [k*P, k*P + P)
        routes = [_sub_select(cand_ref[k * P:(k + 1) * P, :], choice)
                  for k in range(H)]
        doms = [_sub_select(cdom_ref[k * P:(k + 1) * P, :], choice)
                for k in range(H)]
    else:
        routes_ref, rdom_ref = route_refs
        routes = [routes_ref[k:k + 1, :] for k in range(H)]
        doms = [rdom_ref[k:k + 1, :] for k in range(H)]
    w_rate = jnp.where(active, irate, 0.0)

    bg_on = (tick % bg_period).astype(jnp.float32) < \
        bg_duty * bg_period.astype(jnp.float32)

    def bg():
        return bgb_ref[...] + jnp.where(bg_on, bga_ref[...], 0.0)

    def link_add(acc, vals):
        _col_scatter(acc, "add", [(r, vals) for r in routes])

    @pl.when((s == 0) & (b == 0))
    def _init():
        jobmin_s[...] = jnp.full(jobmin_s.shape, _BIG, jnp.int32)
        for r in (offp_s, offhi_s, offlo_s, cnt_s, cntop_s, psnwin_s):
            r[...] = jnp.zeros(r.shape, jnp.float32)
        cand_s[...] = jnp.zeros(cand_s.shape, jnp.int32)
        minact_s[...] = jnp.full(minact_s.shape, _BIG, jnp.int32)

    # ---- sweep 0: job min-wire + proportional offered-load partials
    @pl.when(s == 0)
    def _sweep0():
        _col_scatter(jobmin_s, "min",
                     [(inst_job, jnp.where(active, iwire, _BIG))])
        link_add(offp_s, w_rate)

    def hi_class():
        (jmin,) = _col_take((jobmin_s,), inst_job)
        return active & (iwire <= jmin)

    # ---- sweep 1: hi/lo-class offered partials (min-wire now complete)
    @pl.when(s == 1)
    def _sweep1():
        is_hi = hi_class()
        link_add(offhi_s, jnp.where(is_hi, irate, 0.0))
        link_add(offlo_s, jnp.where(active & ~is_hi, irate, 0.0))

    # ---- sweep 2, first block: finalize the per-link scale factors
    @pl.when((s == 2) & (b == 0))
    def _scales():
        cap = cap_ref[...]
        off_p = offp_s[...] + bg()
        sl_s[...] = jnp.minimum(1.0, cap / jnp.maximum(off_p, 1.0))
        off_hi = offhi_s[...] + bg()
        s_hi = jnp.minimum(1.0, cap / jnp.maximum(off_hi, 1.0))
        shi_s[...] = s_hi
        rem = jnp.maximum(cap - off_hi * s_hi, 0.0)
        slo_s[...] = rem / jnp.maximum(offlo_s[...], 1.0)

    def eff_block():
        is_hi = hi_class()
        path_p = path_hi = path_lo = None
        for r in routes:
            t_l, t_hi, t_lo = _col_take((sl_s, shi_s, slo_s), r)
            share = jnp.where(is_hi, t_hi, jnp.minimum(1.0, t_lo))
            path_p = t_l if path_p is None else jnp.minimum(path_p, t_l)
            path_hi = share if path_hi is None else jnp.minimum(path_hi,
                                                                share)
        eff_p = w_rate * path_p
        eff_q = w_rate * path_hi
        if policy == "pq":
            return eff_q
        return jnp.where(pq_on != 0, eff_q, eff_p)

    def dj_rows():
        return [d * J + inst_job for d in doms]

    # ---- sweep 2, per block: eff + Symphony cnt/cntop/step-min partials
    @pl.when(s == 2)
    def _sweep2():
        eff = eff_block()
        djs = dj_rows()
        pkts = eff * dt / mtu
        newly_done = active & (isent + eff * dt >= ichunk)
        act_pk = jnp.where(active, pkts, 0.0)
        sms = [_col_take((smin_ref,), dj)[0] for dj in djs]
        _col_scatter(cnt_s, "add", [(dj, act_pk) for dj in djs])
        _col_scatter(cntop_s, "add",
                     [(dj, jnp.where(active & (iwire > sm), pkts, 0.0))
                      for dj, sm in zip(djs, sms)])
        done_w = jnp.where(newly_done, iwire + 1, 0)
        _col_scatter(cand_s, "max", [(dj, done_w) for dj in djs])
        open_w = jnp.where(active & ~newly_done, iwire, _BIG)
        _col_scatter(minact_s, "min", [(dj, open_w) for dj in djs])

    # ---- sweep 3, first block: finalize the Symphony step-min
    @pl.when((s == 3) & (b == 0))
    def _stepmin():
        cand = jnp.maximum(smin_ref[...], cand_s[...])
        minact = minact_s[...]
        stepmin_s[...] = jnp.where(minact < _BIG, jnp.minimum(cand, minact),
                                   cand)

    # ---- sweep 3, per block: psn-window partials + per-instance outputs
    @pl.when(s == 3)
    def _sweep3():
        eff = eff_block()
        pkts = eff * dt / mtu
        newly_done = active & (isent + eff * dt >= ichunk)
        send = active & (eff > 1.0) & ~newly_done
        psn = ipsn + pkts
        # state psn-window is always >= 0, so accumulating the >= 0
        # partials from 0 and max-ing with the state at the flush equals
        # the untiled segmax against the state directly
        pairs = []
        for dj in dj_rows():
            (smin,) = _col_take((stepmin_s,), dj)
            pairs.append((dj, jnp.where(send & (iwire == smin), psn, 0.0)))
        _col_scatter(psnwin_s, "max", pairs)
        for k, r in enumerate(routes):
            iroute_o[k:k + 1, :] = r
        eff_o[...] = eff

    # ---- last grid step: flush the link/Symphony outputs
    @pl.when((s == 3) & (b == nb - 1))
    def _flush():
        cap = cap_ref[...]
        off_p = offp_s[...] + bg()
        off_q = (offhi_s[...] + bg()) + offlo_s[...]
        if policy == "pq":
            offered = off_q
        else:
            offered = jnp.where(pq_on != 0, off_q, off_p)
        q = jnp.maximum(q_ref[...] + (offered - cap) * dt, 0.0)
        q = jnp.where(jax.lax.broadcasted_iota(jnp.int32, q.shape, 0) == L,
                      0.0, q)
        offered_o[...] = offered
        q_o[...] = q
        pred_o[...] = jnp.clip((q - red_kmin) / (red_kmax - red_kmin),
                               0.0, 1.0) * red_pmax
        cnt = scnt_ref[...] + cnt_s[...]
        cntop = scntop_ref[...] + cntop_s[...]
        psnwin = jnp.maximum(spsn_ref[...], psnwin_s[...])
        sym_epoch = (tick % sym_win) == (sym_win - 1)
        have = cnt > n_sample
        exceed = cntop >= tau * cnt
        alpha_new = jnp.clip(
            salpha_ref[...] + jnp.where(exceed, 1.0, -1.0) * have,
            1.0, alpha_max)
        smin_o[...] = stepmin_s[...]
        spsn_o[...] = jnp.where(sym_epoch, 0.0, psnwin)
        salpha_o[...] = jnp.where(sym_epoch, alpha_new, salpha_ref[...])
        scnt_o[...] = jnp.where(sym_epoch, 0.0, cnt)
        scntop_o[...] = jnp.where(sym_epoch, 0.0, cntop)


def _edge_pad(x, n):
    """Pad the leading (instance) axis with ``n`` edge rows; lowers to
    slice + concatenate — no gather."""
    if not n:
        return x
    return jnp.pad(x, [(0, n)] + [(0, 0)] * (x.ndim - 1), mode="edge")


def _tiled_tick(operands, tables, *, FW, H, L1, DJ, J, SEG, blk, dt, mtu,
                per_step_ecmp, policy, interpret):
    """Dispatch the tiled grid kernel; see :func:`netsim_tick`."""
    (step_of, sent, rate, done_upto, q_prev,
     s_stepmin, s_psnwin, s_alpha, s_cnt, s_cntop,
     _routes, _path_table, _n_paths, cap, _link_dom, bg_base, bg_amp,
     inst_job, inst_flow, sps_i, phase_i, nph_i, off_i,
     _chunk_sched, iscal, fscal) = operands
    NB = -(-FW // blk)
    FWp = NB * blk
    pad = FWp - FW
    L1p, DJp, Jp = _pad_rows(L1), _pad_rows(DJ), _pad_rows(J)

    def row(x):                        # [FW] -> (1, FWp)
        return _edge_pad(x, pad).reshape(1, FWp)

    def slab(x):                       # [FW, ...] -> (K, FWp), K = hops..
        x = _edge_pad(x, pad)
        return x.reshape(FWp, -1).T if x.ndim == 2 else \
            jnp.transpose(x, (2, 1, 0)).reshape(-1, FWp)

    def col(x, n):                     # [R] -> (n, 1), zero rows appended
        return jnp.pad(x, (0, n - x.shape[0])).reshape(n, 1)

    # done_upto expands [F] -> [FW] at trace time (repeat = broadcast +
    # reshape, gather-free) so it streams with the instance blocks.
    done_i = jnp.repeat(done_upto, FW // int(done_upto.shape[0]))
    ins = [row(step_of), row(sent), row(rate), row(done_i),
           col(q_prev, L1p), col(s_stepmin, DJp), col(s_psnwin, DJp),
           col(s_alpha, DJp), col(s_cnt, DJp), col(s_cntop, DJp),
           col(cap, L1p), col(bg_base, L1p), col(bg_amp, L1p),
           row(inst_job), row(inst_flow), row(sps_i), row(phase_i),
           row(nph_i), row(off_i), slab(tables.chunk)]
    if per_step_ecmp:
        ins += [slab(tables.cand), slab(tables.cand_dom),
                row(tables.n_paths)]
    else:
        ins += [slab(tables.routes), slab(tables.route_dom)]
    ins += [iscal[None], fscal[None]]

    # Per-block valid-lane counts, built from Python ints: lane-INVARIANT,
    # which is what keeps vmap's pallas batching rule on the
    # grid-prepend path (batched scalar-prefetch operands would fall
    # back to a scan over lanes).
    nvalid = jnp.asarray([min(blk, FW - i * blk) for i in range(NB)],
                         jnp.int32)

    def blocked(a):                    # (K, FWp) tiled along lanes
        return pl.BlockSpec((a.shape[0], blk), lambda s, b, nv: (0, b))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda s, b, nv: (0, 0))

    def smem(a):
        return pl.BlockSpec(a.shape, lambda s, b, nv: (0, 0),
                            memory_space=pltpu.SMEM)

    in_specs = [blocked(a) if a.shape[1] == FWp else whole(a)
                for a in ins[:-2]] + [smem(a) for a in ins[-2:]]
    out_shape = (
        [jax.ShapeDtypeStruct((H, FWp), jnp.int32),     # iroute
         jax.ShapeDtypeStruct((1, FWp), jnp.float32)]   # eff
        + [jax.ShapeDtypeStruct((L1p, 1), jnp.float32)] * 3
        + [jax.ShapeDtypeStruct((DJp, 1), jnp.int32)]
        + [jax.ShapeDtypeStruct((DJp, 1), jnp.float32)] * 4)
    out_specs = [blocked(o) if o.shape[1] == FWp else whole(o)
                 for o in out_shape]
    kernel = functools.partial(
        _tiled_tick_kernel, H=H, P=int(tables.cand.shape[1]), J=J, SEG=SEG,
        L=L1 - 1, blk=blk, dt=float(dt), mtu=float(mtu), per_step_ecmp=bool(per_step_ecmp),
        policy=policy)
    f32, i32 = jnp.float32, jnp.int32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(TILED_SWEEPS, NB),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Jp, 1), i32),      # jobmin
            pltpu.VMEM((L1p, 1), f32),     # off_p partials
            pltpu.VMEM((L1p, 1), f32),     # off_hi partials
            pltpu.VMEM((L1p, 1), f32),     # off_lo partials
            pltpu.VMEM((L1p, 1), f32),     # s_l scale
            pltpu.VMEM((L1p, 1), f32),     # s_hi scale
            pltpu.VMEM((L1p, 1), f32),     # s_lo scale
            pltpu.VMEM((DJp, 1), f32),     # cnt partials
            pltpu.VMEM((DJp, 1), f32),     # cntop partials
            pltpu.VMEM((DJp, 1), i32),     # cand partials
            pltpu.VMEM((DJp, 1), i32),     # min-active partials
            pltpu.VMEM((DJp, 1), i32),     # finalized step-min
            pltpu.VMEM((DJp, 1), f32),     # psn-window partials
        ],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="netsim_tick",
    )(nvalid, *ins)
    iroute, eff = outs[0][:, :FW].T, outs[1][0, :FW]
    links = [o[:L1, 0] for o in outs[2:5]]
    sym = [o[:DJ, 0] for o in outs[5:]]
    return TickOut(iroute, eff, *links, *sym)


# --------------------------------------------------------- entry point
def netsim_tick(step_of, sent, rate, done_upto, q_prev,
                s_stepmin, s_psnwin, s_alpha, s_cnt, s_cntop,
                routes, path_table, n_paths, cap, link_dom, bg_base, bg_amp,
                inst_job, inst_flow, sps_i, phase_i, nph_i, off_i,
                chunk_sched, iscal, fscal, *,
                dt: float, mtu: float, per_step_ecmp: bool,
                policy: str = "proportional", segsum: str = "scatter",
                blk: int | None = None, tables=None,
                interpret: bool | None = None) -> TickOut:
    """One fused tick of the netsim hot path.

    Per-instance state is flat ``[FW]``; link state ``[L+1]``; Symphony
    state ``[DJ]``.  ``iscal = [tick, seed, bg_period_ticks,
    sym_win_ticks, pq_on]`` (i32) and ``fscal = [bg_duty, red_kmin,
    red_kmax, red_pmax, tau, n_sample, alpha_max]`` (f32) carry the
    traced scalars; ``dt``/``mtu``/``per_step_ecmp``/``policy``/``blk``
    are compile-time (from :class:`SimStructure`).

    ``blk`` < FW selects the tiled grid kernel (``segsum="onehot"``
    only): per-instance operands are BlockSpec-tiled into ``blk``-lane
    blocks and the grid runs ``(TILED_SWEEPS, ceil(FW/blk))`` steps with
    cross-block reduction partials in persistent scratch.  The tiled
    kernel is gather-free and requires ``tables`` (a
    `params.PackedTables`, normally ``ctx.tables`` from
    `stages.make_ctx`): the packed per-instance route/chunk/ECMP tables
    are streamed block-by-block in place of the index-table operands,
    and the per-block valid counts ride in scalar prefetch.  Compiled
    for a TPU, ``blk`` must be a multiple of 128 (the lane tile).
    """
    if interpret is None:
        interpret = use_interpret()
    if policy not in ("proportional", "pq"):
        raise ValueError(f"kernel share policy must be proportional|pq, "
                         f"got {policy!r}")
    if segsum not in SEGSUM_MODES:
        raise ValueError(f"segsum must be one of {SEGSUM_MODES}, "
                         f"got {segsum!r}")
    FW = step_of.shape[0]
    H = routes.shape[-1]
    L1 = cap.shape[0]
    DJ = s_stepmin.shape[0]
    operands = (step_of, sent, rate, done_upto, q_prev,
                s_stepmin, s_psnwin, s_alpha, s_cnt, s_cntop,
                routes, path_table, n_paths, cap, link_dom, bg_base, bg_amp,
                inst_job, inst_flow, sps_i, phase_i, nph_i, off_i,
                chunk_sched, iscal, fscal)
    if blk is not None:
        if segsum != "onehot":
            raise ValueError(
                f"blk={blk} tiling requires segsum='onehot' (Mosaic has no "
                f"vector scatter), got segsum={segsum!r}")
        if blk < 1:
            raise ValueError(f"blk must be >= 1, got {blk}")
    if blk is not None and blk < FW:
        if tables is None:
            raise ValueError(
                f"blk={blk} tiling requires packed route tables "
                "(params.PackedTables; use ctx.tables from "
                "stages.make_ctx): the gather-free tiled kernel streams "
                "per-instance tables instead of gathering from index-table "
                "operands")
        return _tiled_tick(
            operands, tables, FW=FW, H=H, L1=L1, DJ=DJ,
            J=int(chunk_sched.shape[0]), SEG=int(chunk_sched.shape[-1]),
            blk=int(blk), dt=dt, mtu=mtu, per_step_ecmp=per_step_ecmp,
            policy=policy, interpret=interpret)

    out_shape = [
        jax.ShapeDtypeStruct((FW, H), jnp.int32),   # iroute
        jax.ShapeDtypeStruct((FW,), jnp.float32),   # eff
        jax.ShapeDtypeStruct((L1,), jnp.float32),   # offered
        jax.ShapeDtypeStruct((L1,), jnp.float32),   # q
        jax.ShapeDtypeStruct((L1,), jnp.float32),   # p_red
        jax.ShapeDtypeStruct((DJ,), jnp.int32),     # s_stepmin
        jax.ShapeDtypeStruct((DJ,), jnp.float32),   # s_psnwin
        jax.ShapeDtypeStruct((DJ,), jnp.float32),   # s_alpha
        jax.ShapeDtypeStruct((DJ,), jnp.float32),   # s_cnt
        jax.ShapeDtypeStruct((DJ,), jnp.float32),   # s_cntop
    ]
    kernel = functools.partial(
        _tick_kernel, H=H, SEG=int(chunk_sched.shape[-1]), dt=float(dt),
        mtu=float(mtu), per_step_ecmp=bool(per_step_ecmp), policy=policy,
        segsum=segsum)
    outs = pl.pallas_call(kernel, out_shape=out_shape, interpret=interpret,
                          name="netsim_tick")(*operands)
    return TickOut(*outs)
