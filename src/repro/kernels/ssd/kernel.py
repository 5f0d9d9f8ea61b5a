"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

Grid (B*H, n_chunks); the chunk axis is sequential, carrying the running
inter-chunk state [N, P] in VMEM scratch.  Per chunk (Q = chunk length):

  intra:  y_diag = (C B^T * L) x        (quadratic within the chunk, MXU)
  carry:  y_off  = (C * exp(cum)) state
  update: state  = state * exp(cum[-1]) + (B * decay_to_end)^T x

B/C are shared across the H heads of a batch row (single SSD group), indexed
with bh // H in the BlockSpec index maps.  All accumulation is fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import use_interpret


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, fs_ref, state_ref,
                *, nc, Q):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)            # [Q, P]
    a = a_ref[0].astype(jnp.float32)            # [Q]
    Bm = b_ref[0].astype(jnp.float32)           # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)           # [Q, N]

    cum = jnp.cumsum(a)                         # [Q]
    seg = cum[:, None] - cum[None, :]           # [Q, Q] sum over (j, i]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, Q]
    y = jax.lax.dot_general(G * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q, P]

    state = state_ref[...]                      # [N, P]
    y += jax.lax.dot_general(Cm * jnp.exp(cum)[:, None], state,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    decay_to_end = jnp.exp(cum[-1] - cum)       # [Q]
    state_new = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        Bm * decay_to_end[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [N, P]
    state_ref[...] = state_new

    @pl.when(c_idx == nc - 1)
    def _():
        fs_ref[0] = state_new.astype(fs_ref.dtype)


def ssd_chunked(x, a, Bm, Cm, *, chunk: int, n_heads: int, interpret=None):
    """x: [BH, S, P]; a: [BH, S]; Bm/Cm: [B, S, N] (shared across heads).
    Returns (y [BH,S,P], final_state [BH,N,P])."""
    if interpret is None:
        interpret = use_interpret()
    BH, S, P = x.shape
    N = Bm.shape[-1]
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q
    H = n_heads
    kernel = functools.partial(_ssd_kernel, nc=nc, Q=Q)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q), lambda b, c: (b, c)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b // H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c: (b // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, a, Bm, Cm)
