"""jit'd wrapper: flash attention with custom VJP (Pallas fwd + bwd kernels).

Public entry `flash_attention(q, k, v, q_pos, k_pos, window=0)` matches the
model-side calling convention ([B, S, H, D] layout, contiguous positions).
Interpret or compiled mode follows `repro.kernels.use_interpret`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import flash_bwd, flash_fwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, window, causal):
    o, _ = flash_fwd(q, k, v, scale=1.0 / np.sqrt(q.shape[-1]),
                     window=window, causal=causal)
    return o


def _flash_fwd_rule(q, k, v, window, causal):
    o, lse = flash_fwd(q, k, v, scale=1.0 / np.sqrt(q.shape[-1]),
                       window=window, causal=causal)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(window, causal, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = flash_bwd(q, k, v, o, lse, do,
                           scale=1.0 / np.sqrt(q.shape[-1]),
                           window=window, causal=causal)
    group = q.shape[0] // k.shape[0]
    if group > 1:
        # dk/dv come back per-q-head; reduce over each GQA group
        dk = dk.reshape(k.shape[0], group, *k.shape[1:]).sum(1)
        dv = dv.reshape(v.shape[0], group, *v.shape[1:]).sum(1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, window: int = 0,
                    causal: bool = True) -> jax.Array:
    """q: [B, S, Hq, D]; k/v: [B, S, Hkv, D] -> [B, S, Hq, D].

    Assumes contiguous positions (q_pos/k_pos accepted for API parity with
    the reference; the kernel derives positions from block indices).
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    of = _flash(qf, kf, vf, window, causal)
    return of.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
