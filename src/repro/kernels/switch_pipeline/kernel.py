"""Symphony switch data plane as a Pallas kernel (paper §4.7 analogue).

The Tofino2 prototype processes packets one-per-cycle through stateful ALUs
with only adds/compares and table lookups available — no division.  This
kernel reproduces that pipeline: a sequential walk over a packet batch,
carrying the Per-Job State Block (step_min, psn_rec, alpha, Cnt_total,
Cnt_op) in SMEM scratch, with two marking-probability paths:

  exact=True   float math, bit-identical to core/symphony.py (the oracle)
  exact=False  ASIC path: P and the coin toss compared in log2 domain using
               a 16-entry mantissa lookup table (the paper's "logarithms and
               hardware lookup tables" trick) — state updates stay exact,
               only the stochastic mark decision is approximated.

Inputs per packet: step, psn, LAST bit, window-end flag (T_win boundary),
uniform sample.  Outputs: mark decision + the post-packet (step_min, psn_rec,
alpha) trajectory for exact oracle comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import use_interpret

# 16-entry mantissa log2 LUT: log2(1 + i/16), the kind of table a switch ALU
# indexes with the mantissa's top 4 bits.
_LOG2_LUT = np.log2(1.0 + np.arange(16) / 16.0).astype(np.float32)


def _lut_log2(x: jax.Array, lut: jax.Array) -> jax.Array:
    """Piecewise-constant log2 via exponent extraction + 16-entry LUT."""
    e = jnp.floor(jnp.log2(jnp.maximum(x, 1e-30)))      # exponent (ASIC: CLZ)
    m = x / jnp.exp2(e)                                  # mantissa in [1, 2)
    idx = jnp.clip(((m - 1.0) * 16).astype(jnp.int32), 0, 15)
    return e + lut[idx]


def _pipeline_kernel(lut_ref, steps_ref, psns_ref, lasts_ref, wins_ref, u_ref,
                     marks_ref, smin_ref, prec_ref, alpha_ref,
                     st_ref, *, blk, k, tau, n_warmup, n_sample, alpha_max,
                     exact):
    b = pl.program_id(0)
    lut = lut_ref[...]

    @pl.when(b == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)
        st_ref[2] = jnp.float32(1.0)   # alpha(0) = 1

    def body(i, st):
        step_min, psn_rec, alpha, cnt, cnt_op = st
        step = steps_ref[i].astype(jnp.float32)
        psn = psns_ref[i].astype(jnp.float32)
        is_last = lasts_ref[i] > 0
        win_end = wins_ref[i] > 0
        u = u_ref[i]

        # UpdateTrafficStats (pre-update state)
        is_op = step > step_min
        cnt = cnt + 1.0
        cnt_op = cnt_op + jnp.where(is_op, 1.0, 0.0)

        # marking decision against the found state (Alg. 1 l.11-17)
        outpacing = is_op & (psn_rec > n_warmup)
        if exact:
            p = jnp.minimum(1.0, k * alpha * psn / jnp.maximum(psn_rec, 1.0))
            mark = outpacing & (u < p)
        else:
            # log2-domain compare: log2(u) < log2(k) + log2(alpha) +
            # log2(psn) - log2(psn_rec); min(1, .) becomes sign check.
            lp = (_lut_log2(jnp.float32(k), lut) + _lut_log2(alpha, lut) +
                  _lut_log2(jnp.maximum(psn, 1.0), lut) -
                  _lut_log2(jnp.maximum(psn_rec, 1.0), lut))
            mark = outpacing & (_lut_log2(jnp.maximum(u, 1e-9), lut) < lp)

        # progress tracking (Alg. 1 l.3-10)
        lt = step < step_min
        eq = step == step_min
        step_min = jnp.where(is_last, step + 1.0,
                             jnp.where(lt, step, step_min))
        psn_rec = jnp.where(is_last, 0.0,
                            jnp.where(lt, psn,
                                      jnp.where(eq, jnp.maximum(psn_rec, psn),
                                                psn_rec)))

        # T_win boundary: Eq. 5 integer test + windowed psn reset
        have = cnt > n_sample
        exceed = cnt_op >= tau * cnt
        alpha_w = jnp.clip(alpha + jnp.where(exceed, 1.0, -1.0) * have,
                           1.0, alpha_max)
        alpha = jnp.where(win_end, alpha_w, alpha)
        cnt = jnp.where(win_end, 0.0, cnt)
        cnt_op = jnp.where(win_end, 0.0, cnt_op)
        psn_rec = jnp.where(win_end, 0.0, psn_rec)

        marks_ref[i] = mark.astype(jnp.int32)
        smin_ref[i] = step_min.astype(jnp.int32)
        prec_ref[i] = psn_rec
        alpha_ref[i] = alpha
        return (step_min, psn_rec, alpha, cnt, cnt_op)

    st = (st_ref[0], st_ref[1], st_ref[2], st_ref[3], st_ref[4])
    st = jax.lax.fori_loop(0, blk, body, st)
    st_ref[...] = jnp.stack(st)


def switch_pipeline(steps, psns, lasts, win_ends, uniforms, *,
                    k=0.01, tau=0.25, n_warmup=16, n_sample=32,
                    alpha_max=64.0, exact=True, blk=256, interpret=None):
    """Process a packet batch through Alg. 1.  All inputs [P].
    Returns (marks i32, step_min i32, psn_rec f32, alpha f32) per packet."""
    if interpret is None:
        interpret = use_interpret()
    P = steps.shape[0]
    pad = (-P) % blk
    if pad:
        z = lambda a, v=0: jnp.pad(a, (0, pad), constant_values=v)
        steps, psns = z(steps), z(psns)
        lasts, win_ends = z(lasts), z(win_ends)
        uniforms = z(uniforms, 1.0)
    Pp = steps.shape[0]
    grid = (Pp // blk,)
    kernel = functools.partial(
        _pipeline_kernel, blk=blk, k=float(k), tau=float(tau),
        n_warmup=float(n_warmup), n_sample=float(n_sample),
        alpha_max=float(alpha_max), exact=exact)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((16,), lambda b: (0,))] +
                 [pl.BlockSpec((blk,), lambda b: (b,))] * 5,
        out_specs=[pl.BlockSpec((blk,), lambda b: (b,))] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((Pp,), jnp.int32),
            jax.ShapeDtypeStruct((Pp,), jnp.int32),
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
            jax.ShapeDtypeStruct((Pp,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((5,), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(_LOG2_LUT), steps.astype(jnp.int32),
      psns.astype(jnp.float32), lasts.astype(jnp.int32),
      win_ends.astype(jnp.int32), uniforms.astype(jnp.float32))
    marks, smin, prec, alpha = outs
    return marks[:P], smin[:P], prec[:P], alpha[:P]
