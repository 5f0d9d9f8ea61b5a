"""jit'd wrapper for the SSD kernel, model-side calling convention."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import ssd_chunked


def ssd(x, a, Bm, Cm, *, chunk: int = 128):
    """x: [B, S, H, P] dt-scaled inputs; a: [B, S, H] log decay;
    Bm/Cm: [B, S, N].  Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, Sp, P)
    af = a.transpose(0, 2, 1).reshape(B * H, Sp)
    y, fs = ssd_chunked(xf, af, Bm, Cm, chunk=chunk, n_heads=H)
    y = y.reshape(B, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
    final = fs.reshape(B, H, N, P).transpose(0, 1, 3, 2)   # [B,H,P,N]
    return y, final
