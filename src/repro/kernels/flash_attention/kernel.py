"""Flash attention forward + backward Pallas TPU kernels.

Tiling: grid (batch*heads, q_blocks, kv_blocks); the kv axis is the minormost
grid dimension, so the online-softmax accumulators live in VMEM scratch
across kv iterations (TPU grid order is sequential).  Blocks are 128-aligned
for the MXU; masking covers causal + sliding-window + GQA head-group
mapping (kv rows indexed as (b*Hkv + h // group)).

Backward: two kernels —
  * dq:    grid (BH, iq, jk), accumulate dq over jk in VMEM scratch
  * dk/dv: grid (BH, jk, iq), accumulate dk, dv over iq in VMEM scratch
using the saved LSE and delta = rowsum(dO * O), the standard FlashAttention-2
recomputation scheme.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import use_interpret

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _mask(iq, jk, bq, bk, window, causal, neg=NEG_INF):
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), bool)
    if causal:
        ok &= kpos <= qpos
    if window:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, neg)


# ----------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, window, causal, bq, bk, nk):
    jk = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(jk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip fully-masked blocks (causal upper triangle)
    run = True
    if causal:
        run = (jk * bk) <= (iq * bq + bq - 1)

    @pl.when(run if causal else jk >= 0)
    def _():
        q = q_ref[0].astype(jnp.float32)                 # [bq, d]
        k = k_ref[0].astype(jnp.float32)                 # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(iq, jk, bq, bk, window, causal)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


def flash_fwd(q, k, v, *, scale, window=0, causal=True, bq=DEFAULT_BQ,
              bk=DEFAULT_BK, interpret=None):
    """q: [BH, S, D]; k/v: [BHkv, S, D] with BH = BHkv * group.
    Returns (o [BH,S,D], lse [BH,S])."""
    if interpret is None:
        interpret = use_interpret()
    BH, S, D = q.shape
    BHkv = k.shape[0]
    group = BH // BHkv
    nq, nk = S // bq, S // bk
    grid = (BH, nq, nk)
    kernel = functools.partial(_fwd_kernel, scale=scale, window=window,
                               causal=causal, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ----------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, window, causal, bq, bk, nk):
    jk = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(jk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = (jk * bk) <= (iq * bq + bq - 1)

    @pl.when(run if causal else jk >= 0)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(iq, jk, bq, bk, window, causal)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, window, causal, bq, bk, nq, group):
    iq = pl.program_id(2)
    jk = pl.program_id(1)
    bh = pl.program_id(0)

    @pl.when(iq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = (jk * bk) <= (iq * bq + bq - 1)

    @pl.when(run if causal else iq >= 0)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + _mask(iq, jk, bq, bk, window, causal)
        p = jnp.exp(s - lse_ref[0][:, None])                 # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, None]) * scale        # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bk, d]

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, scale, window=0, causal=True,
              bq=DEFAULT_BQ, bk=DEFAULT_BK, interpret=None):
    """Returns (dq [BH,S,D], dk, dv [BH,S,D] per-q-head; caller reduces
    over GQA groups)."""
    if interpret is None:
        interpret = use_interpret()
    BH, S, D = q.shape
    BHkv = k.shape[0]
    group = BH // BHkv
    nq, nk = S // bq, S // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, window=window,
                          causal=causal, bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, window=window,
                          causal=causal, bq=bq, bk=bk, nq=nq, group=group),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // group, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b // group, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
