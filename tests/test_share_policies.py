"""Isolated oracles for the bandwidth-share stage functions.

The share policies are otherwise only exercised through full-engine runs;
here each is checked against a straightforward NumPy loop oracle on small
hand-built link tables (explicit routes, capacities, weights), plus
behavioral properties (weight splits, deficit redistribution).
"""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.netsim.params import PathIndex, SimParams
from repro.core.netsim.stages import (InstView, share_drr, share_proportional,
                                      share_wfq)


def _mini(routes, active, rate, cap, job=None, weight=None):
    """Hand-built (ctx, inst): N instances with explicit [N, H] routes over
    L real links + the trailing null link (id L, infinite cap)."""
    routes = np.asarray(routes, np.int32)
    n, h = routes.shape
    L = len(cap)
    cap_full = np.append(np.asarray(cap, np.float32), 1e30)
    job = np.zeros(n, np.int32) if job is None else np.asarray(job, np.int32)
    weight = np.ones(int(job.max()) + 1, np.float32) if weight is None \
        else np.asarray(weight, np.float32)
    st = SimpleNamespace(
        cap=jnp.asarray(cap_full),
        job_weight=jnp.asarray(weight),
        bg_base=jnp.zeros(L + 1, jnp.float32),
        bg_amp=jnp.zeros(L + 1, jnp.float32),
        bg_period_ticks=jnp.int32(100),
        bg_duty=jnp.float32(0.0))
    ctx = SimpleNamespace(st=st, L=L, J=int(job.max()) + 1,
                          inst_job=jnp.asarray(job))
    z_i = jnp.zeros(n, jnp.int32)
    z_f = jnp.zeros(n, jnp.float32)
    inst = InstView(
        istep=z_i, isent=z_f, irate=jnp.asarray(rate, jnp.float32),
        iseg=z_i, ichunk=z_f, iwire=jnp.arange(n, dtype=jnp.int32),
        ipsn=z_f, occupied=jnp.asarray(active), retired=jnp.zeros(n, bool),
        complete=jnp.zeros(n, bool), active=jnp.asarray(active),
        iroute=jnp.asarray(routes), flat_links=jnp.asarray(routes.reshape(-1)),
        idom=jnp.zeros((n, h), jnp.int32), dj=jnp.zeros((n, h), jnp.int32),
        djf=jnp.zeros(n * h, jnp.int32),
        # one flow per instance, one route plane each
        paths=PathIndex(links=jnp.asarray(routes[:, None, :]),
                        dom=jnp.zeros((n, 1, h), jnp.int32),
                        dj=jnp.zeros((n, 1, h), jnp.int32)),
        choice=None)
    return ctx, inst, cap_full, routes, job, weight


def _np_offered(routes, w_rate, cap_full):
    offered = np.zeros_like(cap_full)
    for i, r in enumerate(routes):
        for l in r:
            offered[l] += w_rate[i]
    return offered


def test_proportional_matches_numpy_oracle():
    # two insts share link 0 (cap 10); inst 2 alone on link 1 (cap 4)
    ctx, inst, cap_full, routes, _, _ = _mini(
        routes=[[0, 2], [0, 2], [1, 2]],
        active=[True, True, True],
        rate=[8.0, 8.0, 8.0], cap=[10.0, 4.0, 100.0])
    shr = share_proportional(ctx, SimParams(), inst, 0)
    w = np.array([8.0, 8.0, 8.0], np.float32)
    offered = _np_offered(routes, w, cap_full)
    s_l = np.minimum(1.0, cap_full / np.maximum(offered, 1.0))
    eff = w * np.array([s_l[r].min() for r in routes])
    np.testing.assert_allclose(np.asarray(shr.eff), eff, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(shr.offered), offered, rtol=1e-6)
    # link 0 oversubscribed 16/10 -> each gets 5; link 1 at 8/4 -> gets 4
    np.testing.assert_allclose(np.asarray(shr.eff), [5.0, 5.0, 4.0],
                               rtol=1e-6)


def test_proportional_inactive_and_null_link():
    ctx, inst, cap_full, routes, _, _ = _mini(
        routes=[[0, 1], [0, 1]], active=[True, False],
        rate=[50.0, 50.0], cap=[10.0, 10.0])
    shr = share_proportional(ctx, SimParams(), inst, 0)
    eff = np.asarray(shr.eff)
    assert eff[1] == 0.0                      # inactive contributes nothing
    np.testing.assert_allclose(eff[0], 10.0, rtol=1e-6)  # capped by link
    assert np.asarray(shr.offered)[-1] == 0.0  # null link row untouched


def test_wfq_matches_numpy_oracle_and_weight_split():
    # two jobs through the same link, weights 3:1, both rate-unlimited
    ctx, inst, cap_full, routes, job, weight = _mini(
        routes=[[0], [0]], active=[True, True], rate=[100.0, 100.0],
        cap=[8.0], job=[0, 1], weight=[3.0, 1.0])
    shr = share_wfq(ctx, SimParams(share_policy="wfq"), inst, 0)
    w_rate = np.array([100.0, 100.0], np.float32)
    wgt = weight[job]
    wsum = _np_offered(routes, wgt, cap_full)
    fair = np.maximum(cap_full - 0.0, 0.0) / np.maximum(wsum, 1e-9)
    allowed = np.array([wgt[i] * fair[r].min() for i, r in enumerate(routes)])
    eff = np.minimum(w_rate, allowed)
    np.testing.assert_allclose(np.asarray(shr.eff), eff, rtol=1e-6)
    # weight 3 job gets 3x the bandwidth: 6 vs 2 of the 8-unit link
    np.testing.assert_allclose(np.asarray(shr.eff), [6.0, 2.0], rtol=1e-6)
    # offered reports demand, not allocation
    np.testing.assert_allclose(np.asarray(shr.offered)[0], 200.0, rtol=1e-6)


def test_drr_matches_numpy_oracle_with_redistribution():
    # three insts on one 12-unit link; inst 0 wants only 2, so its unused
    # 2 units of the equal 4-unit quantum are redistributed to the others
    ctx, inst, cap_full, routes, _, _ = _mini(
        routes=[[0], [0], [0]], active=[True, True, True],
        rate=[2.0, 100.0, 100.0], cap=[12.0])
    shr = share_drr(ctx, SimParams(share_policy="drr"), inst, 0)
    w_rate = np.array([2.0, 100.0, 100.0], np.float32)
    act = np.array([1.0, 1.0, 1.0], np.float32)
    n_act = _np_offered(routes, act, cap_full)
    avail = np.maximum(cap_full - 0.0, 0.0)
    quantum = avail / np.maximum(n_act, 1.0)
    take1 = np.minimum(w_rate, np.array([quantum[r].min() for r in routes]))
    used = _np_offered(routes, take1, cap_full)
    want = take1 < w_rate
    n_want = _np_offered(routes, want.astype(np.float32), cap_full)
    bonus = np.maximum(avail - used, 0.0) / np.maximum(n_want, 1.0)
    take2 = np.where(
        want, np.minimum(w_rate - take1,
                         np.array([bonus[r].min() for r in routes])), 0.0)
    np.testing.assert_allclose(np.asarray(shr.eff), take1 + take2, rtol=1e-6)
    # 2 + 5 + 5 = 12: the short flow's slack reaches the hungry ones
    np.testing.assert_allclose(np.asarray(shr.eff), [2.0, 5.0, 5.0],
                               rtol=1e-6)


def test_drr_multi_hop_bottleneck():
    # inst 0 crosses links 0 and 1; link 1 (cap 3, shared with inst 1)
    # is the bottleneck, so inst 0's quantum is min over both hops
    ctx, inst, cap_full, routes, _, _ = _mini(
        routes=[[0, 1], [1, 1]], active=[True, True],
        rate=[100.0, 100.0], cap=[20.0, 3.0])
    shr = share_drr(ctx, SimParams(share_policy="drr"), inst, 0)
    eff = np.asarray(shr.eff)
    assert eff[0] <= 3.0 + 1e-5
    # delivered load on the bottleneck stays within capacity
    assert eff[0] + 2 * eff[1] <= 2 * 3.0 + 1e-4


def test_share_helpers_consistency():
    """InstView.link_sum / path_min agree with a NumPy scatter/gather."""
    ctx, inst, cap_full, routes, _, _ = _mini(
        routes=[[0, 1], [1, 2], [2, 0]], active=[True, True, True],
        rate=[1.0, 2.0, 3.0], cap=[5.0, 5.0, 5.0])
    vals = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    got = np.asarray(inst.link_sum(ctx, vals))
    np.testing.assert_allclose(got, _np_offered(routes, np.asarray(vals),
                                                cap_full))
    per_link = jnp.arange(4, dtype=jnp.float32)
    got_min = np.asarray(inst.path_min(per_link))
    want_min = np.array([min(r) for r in routes], np.float32)
    np.testing.assert_allclose(got_min, want_min)
