"""Explicit ring collectives built from `lax.ppermute` (inside shard_map).

These make the 2(N-1)-step structure that Symphony aligns *visible in the
HLO* as chains of collective-permute ops — unlike XLA's fused all-reduce.
The trainer exposes `--grad-sync ring` to synchronize gradients with these
(paper-faithful path); `xla` uses psum (the beyond-paper baseline for the
roofline comparison).

All functions run under shard_map manual axes and operate on the *local
shard* of each device.  Conventions:

  ring_reduce_scatter(x, axis) : x local [n*k, ...] -> [k, ...] reduced shard
  ring_all_gather(x, axis)     : x local [k, ...]   -> [n*k, ...]
  ring_all_reduce(x, axis)     : x local [...]      -> [...] sum over axis

Multi-channel: `channels=c` splits the tensor into c interleaved chunks and
runs c rings concurrently (NCCL channel semantics — exactly the "multiple
parallel 1-D rings" of paper Fig. 1a).  Bidirectional rings split each chunk
in half and pipeline the two directions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _perm(n: int, shift: int = 1):
    return [(i, (i + shift) % n) for i in range(n)]


def ring_reduce_scatter(x: jax.Array, axis: str, reverse: bool = False
                        ) -> jax.Array:
    """x: [n*k, ...] local -> [k, ...]: this device's shard of the sum.

    Step s: each device sends its running partial to the successor and adds
    the local chunk for the shard now being accumulated.  n-1 steps, each
    moving k elements — bandwidth-optimal.  The unrolled permutes appear as
    an explicit collective-permute chain in HLO (the "steps" Symphony
    aligns).
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    k = x.shape[0] // n
    chunks = x.reshape((n, k) + x.shape[1:])
    sgn = -1 if reverse else 1
    perm = _perm(n, sgn)
    acc = jnp.take(chunks, (idx - sgn) % n, axis=0)
    for s in range(1, n):
        acc = jnp.take(chunks, (idx - sgn * (s + 1)) % n, axis=0) + \
            jax.lax.ppermute(acc, axis, perm)
    return acc


def ring_all_gather(x: jax.Array, axis: str, reverse: bool = False
                    ) -> jax.Array:
    """x: [k, ...] local shard -> [n*k, ...] full, ring-pipelined."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    sgn = -1 if reverse else 1
    perm = _perm(n, sgn)
    pieces = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        pieces.append(cur)
    # device idx holds shards [idx, idx-sgn, idx-2sgn, ...]; scatter them into
    # position with a single static concat + roll.
    stack = jnp.stack(pieces)                       # [n, k, ...]
    offs = (idx - sgn * jnp.arange(n)) % n          # source shard ids
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = out.at[offs].set(stack)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def ring_all_reduce(x: jax.Array, axis: str, channels: int = 1,
                    bidirectional: bool = False) -> jax.Array:
    """Flat ring all-reduce = reduce-scatter + all-gather, 2(N-1) steps.

    channels > 1 splits into parallel rings (NCCL channels); bidirectional
    runs half the data around each ring direction.
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % (n * channels * (2 if bidirectional else 1))
    if pad:
        flat = jnp.pad(flat, (0, pad))

    def one_ring(v, reverse):
        rs = ring_reduce_scatter(v, axis, reverse)
        return ring_all_gather(rs, axis, reverse)

    parts = flat.reshape(channels * (2 if bidirectional else 1), -1)
    outs = []
    for c in range(parts.shape[0]):
        rev = bidirectional and (c % 2 == 1)
        outs.append(one_ring(parts[c], rev))
    out = jnp.stack(outs).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape)


def ring_all_reduce_nd(x: jax.Array, axis: str) -> jax.Array:
    """Ring all-reduce chunking along dim 0 WITHOUT flattening: trailing dims
    keep their (auto/TP) sharding, so the permute payload stays the local
    shard.  (Flattening a TP-sharded gradient first forces a 16x all-gather —
    measured in EXPERIMENTS.md §Perf iteration 3.)"""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    orig = x.shape
    if x.ndim == 0:
        x = x.reshape(1)
    pad = (-x.shape[0]) % n
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    out = ring_all_gather(ring_reduce_scatter(x, axis), axis)
    if pad:
        out = out[:-pad]
    return out.reshape(orig)


def hierarchical_all_reduce(x: jax.Array, inner_axis: str, outer_axis: str,
                            channels: int = 1, compress=None) -> jax.Array:
    """Multi-pod gradient sync: ring reduce-scatter intra-pod, ring
    all-reduce of the shard across pods (DCN hop — the tier the paper's
    fabric represents), then ring all-gather intra-pod.

    Wire cost per chip: 2S(n-1)/n intra + 2S'(p-1)/p inter with S' = S/n —
    the inter-pod traffic is 1/n of a naive flat all-reduce across all chips.
    `compress` = (encode, decode) pair applied around the inter-pod hop
    (e.g. int8 error-feedback, optim/compress.py).
    """
    n = jax.lax.axis_size(inner_axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % (n * channels)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = ring_reduce_scatter(flat, inner_axis)
    if compress is not None:
        encode, decode = compress
        shard_q, meta = encode(shard)
        shard_q = ring_all_reduce(shard_q, outer_axis, channels=channels)
        shard = decode(shard_q, meta)
    else:
        shard = ring_all_reduce(shard, outer_axis, channels=channels)
    out = ring_all_gather(shard, inner_axis)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)
