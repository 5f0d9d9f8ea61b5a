"""Plain jax.numpy reference of the fluid ring-collective network simulator.

One tick of one lane follows the paper's model in ten steps: start new
step-sends (ring data dependency, segment barrier, free window slot);
pick each step's path by the per-step ECMP hash; share link bandwidth
(proportional fluid max-min); integrate queues and the RED profile; mark
(RED x Symphony, Eq. 1 and 4); advance bytes and retire steps in order;
update Symphony's per-(switch, job) state blocks (Alg. 1, windowed
alpha); DCQCN rate control every epoch, with the coin flips drawn from
the lane's key (``key, sub = split(key); u = uniform(sub)``); segment
barriers and job finish; and, on the last tick of each record period,
the sampled observables.  Lanes are ``vmap``-ed, ticks ``scan``-ned.

Floats are computed in ``dtype``: float32, the precision the simulator
states, for the reference; the control passes a lower one.  It runs on
whatever device JAX gives it, so on the chip its ``exp``, ``log1p`` and
division are the chip's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WIRE_SEG = 4096
BIG = 2 ** 30
IMAX = 2 ** 31 - 1
INT_KNOBS = ("cc_epoch_ticks", "cc_fr_stages", "sym_on", "sym_win_ticks",
             "sym_start_tick", "seed")
FLOAT_KNOBS = ("red_kmin", "red_kmax", "red_pmax", "cc_g", "cc_rai",
               "cc_rhai", "cc_min_rate", "k", "tau", "n_warmup", "n_sample",
               "alpha_max")
STATE = ("next_step", "done_upto", "finish", "step_of", "sent", "rate",
         "target", "alpha_cc", "stage", "lam", "q", "s_stepmin", "s_psnwin",
         "s_alpha", "s_cnt", "s_cntop", "seg_idx", "seg_ready", "job_finish",
         "key")
SERIES = ("min_wire", "max_wire", "done_min", "tput", "qmax", "alpha_max")


def knob_lanes(points: list[dict], seeds, dtype) -> dict:
    """Per-lane knob columns; floats as stated in float32, then ``dtype``."""
    out = {n: jnp.asarray(np.array([p[n] for p in points]), jnp.int32)
           for n in INT_KNOBS if n != "seed"}
    out["seed"] = jnp.asarray(np.asarray(seeds), jnp.int32)
    for n in FLOAT_KNOBS:
        col = np.array([p[n] for p in points], np.float32)
        out[n] = jnp.asarray(col).astype(dtype)
    return out


def _seg(c, sps, phase, nph):
    return (c // sps) * nph + phase


class Engine:
    """The reference for one deployment (``build.Deployment``)."""

    def __init__(self, dep, engine: dict, dtype=jnp.float32):
        if engine.get("share_policy", "proportional") != "proportional" \
                or not engine.get("per_step_ecmp", True):
            raise ValueError("the reference models proportional sharing "
                             "with per-step ECMP only")
        self.dep, self.fdt = dep, dtype
        self.R = int(engine["record_every"])
        self.step_dt = float(np.float32(engine["dt"]))
        self.mtu = float(np.float32(engine["mtu"]))

    def init_state(self, keys) -> dict:
        dep, f = self.dep, self.fdt
        K = keys.shape[0]
        F, W, J = dep.F, dep.window, dep.J
        DJ = (dep.D + 1) * J
        i = lambda *s, v=0: jnp.full((K,) + s, v, jnp.int32)
        x = lambda *s, v=0.0: jnp.full((K,) + s, v, f)
        lr = jnp.broadcast_to(jnp.asarray(dep.line_rate, f)[None, :, None],
                              (K, F, W))
        ready = np.where(dep.trig_job >= 0, IMAX, dep.seg_ready0 + dep.gap)
        return dict(
            next_step=i(F), done_upto=i(F), finish=i(F, v=IMAX),
            step_of=i(F, W, v=-1), sent=x(F, W), rate=lr, target=lr,
            alpha_cc=x(F, W, v=1.0), stage=i(F, W), lam=x(F, W),
            q=x(dep.L + 1), s_stepmin=i(DJ), s_psnwin=x(DJ),
            s_alpha=x(DJ, v=1.0), s_cnt=x(DJ), s_cntop=x(DJ), seg_idx=i(J),
            seg_ready=jnp.broadcast_to(jnp.asarray(ready, jnp.int32), (K, J)),
            job_finish=i(J, v=IMAX), key=jnp.asarray(keys, jnp.uint32))

    def run(self, state: dict, knobs: dict, tick0: int, n_ticks: int):
        """``(state, series)`` after ``n_ticks`` ticks from ``tick0``:
        every series ``[K, n_ticks // record_every, ...]``."""
        if n_ticks % self.R:
            raise ValueError("n_ticks must be a multiple of record_every")
        state = {k: jnp.asarray(state[k]) for k in STATE}
        S, series = _run(self, state, knobs, jnp.int32(tick0),
                         n_ticks // self.R)
        return S, dict(zip(SERIES, (jnp.swapaxes(x, 0, 1) for x in series)))

    def tick(self, S, kn, t):
        dep, f = self.dep, self.fdt
        F, W, J, L, D = dep.F, dep.window, dep.J, dep.L, dep.D
        H = dep.paths.shape[-1]
        FW = F * W
        DJ = (D + 1) * J
        dt, mtu = f(self.step_dt), f(self.mtu)
        job, pred = jnp.asarray(dep.job), jnp.asarray(dep.pred)
        sps, phase = jnp.asarray(dep.sps), jnp.asarray(dep.phase)
        nph = jnp.asarray(dep.n_phases[dep.job])
        chunk = jnp.asarray(dep.chunk, f)
        line = jnp.asarray(dep.line_rate, f)
        cap = jnp.asarray(dep.cap, f)
        fidx = jnp.arange(F)

        def chunk_at(j, seg):
            return chunk[j, jnp.clip(seg, 0, chunk.shape[1] - 1)]

        # 1. starts: ring dependency, segment barrier, free window slot
        s = S["next_step"]
        seg_ok = (_seg(s, sps, phase, nph) == S["seg_idx"][job]) & \
            (t >= S["seg_ready"][job])
        w_prev = (s - 1) % W
        ps_prev = S["step_of"][pred, w_prev]
        prev_chunk = chunk_at(job, _seg(s - 1, sps, phase, nph))
        pred_done = (S["done_upto"][pred] >= s) | (ps_prev > s - 1) | \
            ((ps_prev == s - 1) & (S["sent"][pred, w_prev] >= prev_chunk))
        pass_done = (S["done_upto"] >= s) & (S["done_upto"][pred] >= s)
        ring_ok = jnp.where(s % jnp.asarray(dep.pass_steps) == 0,
                            (s == 0) | pass_done, pred_done) & \
            (t >= jnp.asarray(dep.fstart))
        w_next = s % W
        slot = S["step_of"][fidx, w_next]
        can = (s < jnp.asarray(dep.total_steps)) & seg_ok & ring_ok & \
            ((slot < 0) | (slot < S["done_upto"]))

        def start(a, v):
            return a.at[fidx, w_next].set(jnp.where(can, v, a[fidx, w_next]))

        step_of = start(S["step_of"], s)
        sent = start(S["sent"], f(0))
        rate = start(S["rate"], line)
        target = start(S["target"], line)
        alpha_cc = start(S["alpha_cc"], f(1))
        stage = start(S["stage"], 0)
        lam = start(S["lam"], f(0))

        # 2. instances and their per-step ECMP paths
        ifl = jnp.repeat(fidx, W)
        ijob = job[ifl]
        istep = step_of.reshape(FW)
        isent = sent.reshape(FW)
        irate = rate.reshape(FW)
        iseg = _seg(istep, sps[ifl], phase[ifl], nph[ifl])
        ichunk = chunk_at(ijob, iseg)
        iwire = iseg * WIRE_SEG + istep % sps[ifl] + \
            jnp.asarray(dep.step_offset)[ifl]
        occupied = istep >= 0
        retired = occupied & (istep < S["done_upto"][ifl])
        complete = occupied & (isent >= ichunk)
        active = occupied & ~complete & ~retired
        u32 = jnp.uint32
        h = (ifl.astype(u32) * u32(2654435761)
             + jnp.maximum(istep, 0).astype(u32) * u32(40503)
             + (kn["seed"].astype(u32) + u32(1)) * u32(2246822519))
        h = (h ^ (h >> 13)) * u32(2654435761)
        h = h ^ (h >> 16)
        n_paths = jnp.asarray(dep.n_paths)[ifl].astype(u32)
        route = jnp.asarray(dep.paths)[ifl, (h % n_paths).astype(jnp.int32)]
        hop = route.reshape(-1)                       # [FW*H]

        def per_hop(x):
            return jnp.repeat(x, H)

        # 3. proportional bandwidth sharing
        w_rate = jnp.where(active, irate, f(0))
        offered = jnp.zeros(L + 1, f).at[hop].add(per_hop(w_rate))
        share = jnp.minimum(f(1), cap / jnp.maximum(offered, f(1)))
        eff = w_rate * share[route].min(axis=1)

        # 4. queues and RED
        q = jnp.maximum(S["q"] + (offered - cap) * dt, f(0)).at[L].set(0)
        p_red = jnp.clip((q - kn["red_kmin"]) /
                         (kn["red_kmax"] - kn["red_kmin"]), 0, 1) \
            * kn["red_pmax"]

        # 5. marking: RED x Symphony selective marking
        dom = jnp.asarray(dep.dom)[route]
        dj = dom * J + ijob[:, None]
        sm = S["s_stepmin"][dj]
        pw = S["s_psnwin"][dj]
        al = S["s_alpha"][dj]
        ipsn = isent / mtu
        p_sym = jnp.minimum(f(1), kn["k"] * (al * (ipsn[:, None] /
                                                   jnp.maximum(pw, f(1)))))
        on = (kn["sym_on"] != 0) & (t >= kn["sym_start_tick"])
        p_sym = jnp.where((iwire[:, None] > sm) & (pw > kn["n_warmup"]) &
                          (dom < D) & on, p_sym, f(0))
        p_hop = f(1) - (f(1) - p_red[route]) * (f(1) - p_sym)
        p_inst = f(1) - jnp.exp(jnp.sum(
            jnp.log1p(-jnp.minimum(p_hop, f(0.999999))), axis=1))
        pkts = eff * dt / mtu
        lam = (lam.reshape(FW) + jnp.where(active, p_inst * pkts, f(0))
               ).reshape(F, W)

        # 6. progress: bytes, in-order retirement, flow finish
        isent_new = isent + eff * dt
        newly_done = active & (isent_new >= ichunk)
        sent = isent_new.reshape(F, W)
        done = S["done_upto"]
        for _ in range(2):
            w = done % W
            ch = chunk_at(job, _seg(done, sps, phase, nph))
            done = done + ((step_of[fidx, w] == done) &
                           (sent[fidx, w] >= ch)).astype(jnp.int32)
        total = jnp.asarray(dep.total_steps)
        finish = jnp.where((done >= total) & (S["finish"] == IMAX), t,
                           S["finish"])

        # 7. Symphony state blocks (Alg. 1)
        rows = dj.reshape(-1)
        act4, done4 = per_hop(active), per_hop(newly_done)
        wire4, pk4 = per_hop(iwire), per_hop(pkts)
        sm4 = sm.reshape(-1)
        cnt = S["s_cnt"].at[rows].add(jnp.where(act4, pk4, f(0)))
        cntop = S["s_cntop"].at[rows].add(
            jnp.where(act4 & (wire4 > sm4), pk4, f(0)))
        cand = jnp.zeros(DJ, jnp.int32).at[rows].max(
            jnp.where(done4, wire4 + 1, 0))
        cand = jnp.maximum(S["s_stepmin"], cand)
        min_act = jnp.full(DJ, BIG, jnp.int32).at[rows].min(
            jnp.where(act4 & ~done4, wire4, BIG))
        stepmin = jnp.where(min_act < BIG, jnp.minimum(cand, min_act), cand)
        send4 = per_hop(active & (eff > 1))
        psnwin = S["s_psnwin"].at[rows].max(jnp.where(
            send4 & ~done4 & (wire4 == stepmin[rows]), per_hop(ipsn + pkts),
            f(0)))
        epoch = (t % kn["sym_win_ticks"]) == kn["sym_win_ticks"] - 1
        have = cnt > kn["n_sample"]
        exceed = cntop >= kn["tau"] * cnt
        alpha_new = jnp.clip(S["s_alpha"] + jnp.where(exceed, f(1), f(-1)) *
                             have.astype(f), f(1), kn["alpha_max"])
        s_alpha = jnp.where(epoch, alpha_new, S["s_alpha"])
        cnt = jnp.where(epoch, f(0), cnt)
        cntop = jnp.where(epoch, f(0), cntop)
        psnwin = jnp.where(epoch, f(0), psnwin)

        # 8. DCQCN rate control, every cc epoch
        cc = (t % kn["cc_epoch_ticks"]) == kn["cc_epoch_ticks"] - 1
        key_next, sub = jax.random.split(S["key"])
        u = jax.random.uniform(sub, (F, W)).astype(f)
        cut = (u < f(1) - jnp.exp(-lam)) & (step_of >= 0)
        g = kn["cc_g"]
        fr = kn["cc_fr_stages"]
        r_cut = jnp.maximum(rate * (f(1) - alpha_cc / f(2)), kn["cc_min_rate"])
        t_cut = jnp.where(stage > 0, rate, target)
        stage_n = stage + 1
        inc = jnp.where(stage_n > fr, jnp.where(stage_n > 2 * fr, kn["cc_rhai"],
                                                kn["cc_rai"]), f(0))
        t_n = jnp.minimum(target + inc, line[:, None])
        r_n = jnp.minimum((rate + t_n) / f(2), line[:, None])
        rate = jnp.where(cc, jnp.where(cut, r_cut, r_n), rate)
        target = jnp.where(cc, jnp.where(cut, t_cut, t_n), target)
        alpha_cc = jnp.where(cc, jnp.where(cut, (f(1) - g) * alpha_cc + g,
                                           (f(1) - g) * alpha_cc), alpha_cc)
        stage = jnp.where(cc, jnp.where(cut, 0, stage_n), stage)
        lam = jnp.where(cc, f(0), lam)
        key = jnp.where(cc, key_next, S["key"])

        # 9. segment barriers, job finish, triggered arrivals
        n_ph, n_segs = jnp.asarray(dep.n_phases), jnp.asarray(dep.n_segs)
        seg_idx, ready0 = S["seg_idx"], S["seg_ready"]
        part = phase == (seg_idx % n_ph)[job]
        c_end = (seg_idx[job] // nph + 1) * sps
        flow_done = (~part | (done >= c_end)).astype(jnp.int32)
        seg_done = jnp.ones(J, jnp.int32).at[job].min(flow_done) > 0
        adv = seg_done & (seg_idx < n_segs) & (t >= ready0)
        seg_idx = seg_idx + adv.astype(jnp.int32)
        gap = jnp.asarray(dep.gap)
        seg_ready = jnp.where(adv, t + jnp.where(seg_idx % n_ph == 0, gap, 0),
                              ready0)
        job_finish = jnp.where((seg_idx >= n_segs) &
                               (S["job_finish"] == IMAX), t, S["job_finish"])
        trig = jnp.asarray(dep.trig_job)
        fired = (trig >= 0) & (ready0 == IMAX) & \
            (seg_idx[jnp.clip(trig, 0, J - 1)] >= jnp.asarray(dep.trig_seg))
        seg_ready = jnp.where(fired, t + jnp.asarray(dep.trig_delay) + gap,
                              seg_ready)

        S = dict(next_step=jnp.where(can, s + 1, s), done_upto=done,
                 finish=finish, step_of=step_of, sent=sent, rate=rate,
                 target=target, alpha_cc=alpha_cc, stage=stage, lam=lam, q=q,
                 s_stepmin=stepmin, s_psnwin=psnwin, s_alpha=s_alpha,
                 s_cnt=cnt, s_cntop=cntop, seg_idx=seg_idx,
                 seg_ready=seg_ready, job_finish=job_finish, key=key)

        # 10. sampled observables
        of_job = ijob[None, :] == jnp.arange(J)[:, None]          # [J, FW]
        act_j = of_job & active[None, :]
        flows_j = job[None, :] == jnp.arange(J)[:, None]          # [J, F]
        sample = (jnp.where(act_j, iwire[None, :], BIG).min(axis=1),
                  jnp.where(act_j, iwire[None, :], -1).max(axis=1),
                  jnp.where(flows_j, done[None, :], BIG).min(axis=1),
                  jnp.where(of_job, eff[None, :], f(0)).sum(axis=1),
                  q[:L].max(), s_alpha.max())
        return S, sample


@partial(jax.jit, static_argnums=(0, 4))
def _run(eng: Engine, S, kn, tick0, n_rec):
    lane_tick = jax.vmap(eng.tick, in_axes=(0, 0, None))

    def period(S, r):
        def body(S, i):
            return lane_tick(S, kn, tick0 + r * eng.R + i)
        S, samples = jax.lax.scan(body, S, jnp.arange(eng.R))
        return S, jax.tree.map(lambda x: x[-1], samples)

    return jax.lax.scan(period, S, jnp.arange(n_rec))
