"""The comparison that decides a run's ``correct``.

What the timed path produced is set beside the plain reference
(``bench/reference``), run on the same deployment, knob values and lane
seeds: for a sweep, from tick 0 over the whole dispatch; for an online
step, from the program's own state before the step, with the step's
knob values.  The outputs are reduced to a few numbers, each held to a
limit of the cell's own (``limits/<cell>.json``):

* ``int_mismatch``: integers that differ (the sampled oldest and newest
  wire steps and completed-step counts, flow and job finish ticks, and
  for a step every integer leaf of the engine state);
* ``tput_gap``, ``qmax_gap``, ``alpha_gap``: the widest gap of the
  sampled job throughput, largest queue and largest Symphony alpha, as
  a share of the reference's peak of that series in that lane;
* ``state_gap`` (a step): the widest gap of a float state leaf, as a
  share of the larger of that leaf's peak and the median leaf's peak.
"""
from __future__ import annotations

import numpy as np

INT_SERIES = ("min_wire", "max_wire", "done_min")
FLOAT_SERIES = {"tput": "tput_gap", "qmax": "qmax_gap",
                "alpha_max": "alpha_gap"}


def _lane_gap(p, r):
    """max over lanes of max|p - r| / max(peak |r| in the lane, 1)."""
    p = np.asarray(p, np.float64)
    r = np.asarray(r, np.float64)
    if not (np.isfinite(p).all() and p.shape == r.shape):
        return float("inf")
    ax = tuple(range(1, r.ndim))
    peak = np.maximum(np.abs(r).max(axis=ax), 1.0)
    return float((np.abs(p - r).max(axis=ax) / peak).max())


def _mismatch(p, r):
    p, r = np.asarray(p), np.asarray(r)
    if p.shape != r.shape:
        return int(r.size)
    return int((p != r).sum())


def series_readings(prog: dict, ref: dict) -> dict:
    """Numbers of sampled series (dicts of ``[K, T, ...]`` arrays keyed
    as ``engine.SERIES``; ``finish``/``job_finish`` compared when both
    sides hold them)."""
    out = {"int_mismatch": sum(_mismatch(prog[n], ref[n])
                               for n in INT_SERIES)}
    for n in ("finish", "job_finish"):
        if n in prog and n in ref:
            out["int_mismatch"] += _mismatch(prog[n], ref[n])
    for n, key in FLOAT_SERIES.items():
        out[key] = _lane_gap(prog[n], ref[n])
    return out


def state_readings(prog: dict, ref: dict) -> dict:
    """``int_mismatch`` and ``state_gap`` of two engine states."""
    ints, gaps, peaks = 0, {}, {}
    for k, r in ref.items():
        r = np.asarray(r)
        p = np.asarray(prog[k])
        if r.dtype.kind in "iu":
            ints += _mismatch(p, r)
            continue
        r64, p64 = r.astype(np.float64), p.astype(np.float64)
        peaks[k] = float(np.abs(r64).max()) if r64.size else 0.0
        if p64.shape != r64.shape or not np.isfinite(p64).all():
            gaps[k] = float("inf")
        else:
            gaps[k] = float(np.abs(p64 - r64).max()) if r64.size else 0.0
    floor = float(np.median(list(peaks.values()))) if peaks else 1.0
    worst = max((g / max(peaks[k], floor, 1e-30) for k, g in gaps.items()),
                default=0.0)
    return {"int_mismatch": ints, "state_gap": worst}


def merge(readings: list[dict]) -> dict:
    """The worst of each number over several compared units."""
    out = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit.  A number
    that is missing or not finite fails."""
    check, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        check[name] = {"value": v if v is None or np.isfinite(v) else str(v),
                       "limit": limit}
    return ok, check
