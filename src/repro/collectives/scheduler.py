"""Step-aligned gradient-bucket scheduler (host-side Symphony counterpart).

The in-network mechanism (core/symphony.py) aligns ring steps *inside the
fabric*; the framework keeps the sender side aligned by

  1. bucketizing gradients into fixed-size buckets (NCCL-style), so every
     ring step moves a uniform volume (the paper's uniformity assumption,
     §3.2 "Traffic granularity"),
  2. issuing buckets in reverse layer order (sync overlaps backward compute),
  3. shrinking the bucket size when the straggler monitor reports high
     step-time jitter — smaller steps bound the damage a single slow step
     can do (the chunk-size effect of paper Fig. 8c).

`sync_grads_local` must be called INSIDE a shard_map region that is manual
over the data axes (see runtime/train.py `make_train_step(grad_sync="ring")`)
— partial per-device gradients are only representable there.  The 'model'
axis stays auto (GSPMD), so TP collectives coexist with the explicit rings.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .ring import (hierarchical_all_reduce, ring_all_reduce,
                   ring_all_reduce_nd)


@dataclass(frozen=True)
class BucketPlan:
    bucket_of: tuple[tuple[int, ...], ...]   # leaf indices per bucket
    bucket_bytes: int


def plan_buckets(sizes: list[int], bucket_bytes: int = 32 << 20,
                 dtype_bytes: int = 4) -> BucketPlan:
    """Greedy reverse-order bucketing (grads become ready last-layer-first)."""
    buckets: list[list[int]] = [[]]
    acc = 0
    for i in reversed(range(len(sizes))):
        buckets[-1].append(i)
        acc += sizes[i] * dtype_bytes
        if acc >= bucket_bytes:
            buckets.append([])
            acc = 0
    if not buckets[-1]:
        buckets.pop()
    return BucketPlan(bucket_of=tuple(tuple(b) for b in buckets),
                      bucket_bytes=bucket_bytes)


def sync_grads_local(grads, axes: tuple[str, ...], *, mode: str = "ring",
                     channels: int = 4, bidirectional: bool = False,
                     bucket_bytes: int = 32 << 20, compress=None,
                     mean: bool = True):
    """All-reduce a gradient pytree over manual mesh `axes`.

    mode: 'ring' (flat rings over each axis), 'hierarchical' (intra-pod ring
    reduce-scatter + inter-pod ring on the shard + intra-pod all-gather), or
    'psum' (XLA collective — the comparison baseline).

    compress: optional (encode, decode) from optim/compress.py applied around
    the inter-pod hop of hierarchical sync (error-feedback int8).
    """
    leaves, treedef = jax.tree.flatten(grads)
    if not axes:
        return grads
    n_total = 1
    for ax in axes:
        n_total *= jax.lax.axis_size(ax)

    if mode == "psum":
        out = [jax.lax.psum(l, axes) for l in leaves]
        if mean:
            out = [o / n_total for o in out]
        return jax.tree.unflatten(treedef, out)

    from .. import flags
    wire_dtype = jnp.dtype(flags.RING_SYNC_DTYPE)
    # Leaf-wise rings chunked along dim 0: flattening TP-sharded gradients
    # into one buffer would force an all-gather over the model axis first
    # (16x the wire — §Perf iteration 3).  Buckets still gate issue order.
    sizes = [int(np.prod(l.shape)) for l in leaves]
    plan = plan_buckets(sizes, bucket_bytes)
    out_leaves: list = [None] * len(leaves)
    for bucket in plan.bucket_of:
        for i in bucket:
            g = leaves[i].astype(wire_dtype)
            if mode == "hierarchical" and "pod" in axes and len(axes) == 2:
                inner = axes[1] if axes[0] == "pod" else axes[0]
                red = hierarchical_all_reduce(
                    g.reshape(-1), inner_axis=inner, outer_axis="pod",
                    channels=channels, compress=compress).reshape(g.shape)
            else:
                red = g
                for ax in axes:
                    red = ring_all_reduce_nd(red, ax)
            if mean:
                red = red / n_total
            out_leaves[i] = red.astype(leaves[i].dtype)
    return jax.tree.unflatten(treedef, out_leaves)
