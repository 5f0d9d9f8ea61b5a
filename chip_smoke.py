#!/usr/bin/env python3
"""Bring-up smoke test of the Symphony simulator on one TPU chip.

Drives the simulator's main path through its user entry points, in one
process, and checks the results against the staged XLA engine:

1. ``table1_ring`` at the registry's settings (32 hosts, 4 ToR x 4
   spine, 8-host rings, 8 MB chunks, 6 passes), its whole 18-point knob
   grid as one ``simulate_grid`` batch on ``backend="xla"``.  Only the
   horizon is cut (``TABLE1_HORIZON_MULT``): the full 215,039 ticks take
   over an hour on one chip;
2. the same grid on the compiled tiled kernel (``backend="pallas"``,
   ``segsum="onehot"``, ``blk=256``): integer outputs must equal step 1
   lane for lane;
3. the Table-1 golden scenario (1 MB chunks, 2 passes, seed 3), in which
   the job finishes, on both backends: the kernel's integers must equal
   XLA's, and XLA's ``job_finish`` is printed next to the CPU golden (a
   gap is reported, not failed: the chip's transcendentals differ from
   the CPU's);
4. ``fat_tree_multipod`` at 512 hosts through ``SimController.step()``
   for a few windows on both backends: the windowed run must equal one
   window over the same ticks (integer state and ``ts_alpha_max`` bit
   for bit), the kernel's integers must equal XLA's, and no lane may
   hold NaN.

``--four-chips`` runs only the sharded lane grid instead: 8 lanes of the
512-host ``fat_tree_multipod`` on the XLA engine over 4 chips
(``devices=4``), compared with the same lanes on one chip, over a cut
horizon (``MULTIPOD_GRID_HORIZON_MULT``).

Each phase prints one JSON line (compile and run seconds, lanes, ticks,
peak device bytes, device kind).  The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
There is no CPU fallback.  Run from the repository root::

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
from jax import monitoring

ROOT = Path(__file__).resolve().parent
KERNEL = dict(backend="pallas", segsum="onehot", blk=256)
INT_FIELDS = ("finish_ticks", "job_finish_ticks", "ts_min_wire",
              "ts_max_wire", "ts_done_min")
# Horizons, as multiples of the ideal CCT (the registry's default is 4).
# On one v5e the XLA engine takes ~22 ms per tick for 18 Table-1 lanes
# and ~36 ms per tick for one 512-host lane, so the full horizons cannot
# finish inside a smoke run.
TABLE1_HORIZON_MULT = 0.2           # 10,752 of 215,039 ticks
MULTIPOD_GRID_HORIZON_MULT = 0.04   # 198 of 19,839 ticks
WINDOW_TICKS = 100          # 512-host control window (5 record periods)
N_WINDOWS = 3


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading a
    compiled program from the persistent cache) since the last reset."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.cache_hits = 0.0, 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded lane grid and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the grid lanes (routing draws and CC "
                         "coin flips)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeError(f"{ROOT} holds no src/repro: run from the "
                         "repository root")
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        raise SmokeError("REPRO_PALLAS_INTERPRET is set; the smoke runs "
                         "compiled kernels only")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX found {devices[0].platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SmokeError(f"need {need} chips, JAX found {len(devices)}")

    from benchmarks.common import enable_compile_cache
    from repro.kernels import use_interpret
    check(not use_interpret(), "use_interpret() chose interpret mode on TPU")
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    dev = devices[0]
    print(json.dumps({"phase": "start", "device_kind": dev.device_kind,
                      "count": len(devices), "compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)

    if args.four_chips:
        four_chip_grid(clock, dev, args.seed)
    else:
        table1_grids(clock, dev, args.seed, horizon_mult=TABLE1_HORIZON_MULT)
        table1_golden(clock, dev)
        multipod_windows(clock, dev, args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


# ------------------------------------------------------------ helpers
def report(clock, dev, phase, wall, **fields):
    stats = dev.memory_stats() or {}
    line = {"phase": phase, "compile_s": round(clock.seconds, 3),
            "run_s": round(wall - clock.seconds, 3),
            "cache_hits": clock.cache_hits,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "device_kind": dev.device_kind, **fields}
    print(json.dumps(line), flush=True)
    clock.reset()


def timed(clock, fn):
    clock.reset()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def int_fields(tree) -> list[str]:
    return [f for f in tree._fields
            if np.asarray(getattr(tree, f)).dtype.kind in "iu"]


def int_mismatches(a, b, fields):
    return [f for f in fields
            if not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f)))]


def all_finite(tree) -> bool:
    return all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(tree)
               if np.asarray(x).dtype.kind == "f")


# ------------------------------------------------------------- phases
def table1_grids(clock, dev, seed, **overrides):
    """Phases 1-2: the Table-1 knob grid on both backends (``overrides``
    go to the scenario builder)."""
    from benchmarks.common import run_grid, run_scenario_grid
    from repro.core.netsim.stages import resolve_backend

    (built, cfgs, res_x), wall = timed(clock, lambda: run_scenario_grid(
        "table1_ring", seeds=(seed,), devices=None, **overrides))
    lanes, ticks = len(cfgs), cfgs[0].n_ticks
    check(all_finite(res_x), "table1 xla: non-finite output")
    report(clock, dev, "table1_xla", wall, lanes=lanes, ticks=ticks,
           lane_ticks_per_s=round(lanes * ticks / max(wall - clock.seconds,
                                                      1e-9), 1))

    cfgs_k = [c._replace(**KERNEL) for c in cfgs]
    check(resolve_backend(cfgs_k[0]) == "pallas",
          "table1 kernel config does not resolve to the pallas backend")
    res_k, wall = timed(clock, lambda: run_grid(
        built.topo, built.wl, cfgs_k, (seed,), routing=built.routing,
        devices=None))
    check(all_finite(res_k), "table1 kernel: non-finite output")
    bad = int_mismatches(res_x, res_k, INT_FIELDS)
    report(clock, dev, "table1_kernel", wall, lanes=lanes, ticks=ticks,
           lane_ticks_per_s=round(lanes * ticks / max(wall - clock.seconds,
                                                      1e-9), 1),
           int_equal_to_xla=not bad)
    check(not bad, f"table1: kernel integer outputs differ from XLA in {bad}")


def table1_golden(clock, dev):
    """Phase 3: the Table-1 golden scenario on both backends; the chip's
    ECMP baseline beside the CPU golden."""
    from repro.core.netsim import (SimParams, WorkloadBuilder,
                                   make_leaf_spine, simulate)
    from tests.netsim_goldens import GOLDEN_JOB

    topo = make_leaf_spine(32, 4, 4)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(32)), ring_size=8, chunk_bytes=1e6,
                   passes=2, barrier=False)
    wl = b.build()
    cfg = SimParams(n_ticks=20_000, window=64)
    res_x, wall = timed(clock, lambda: simulate(
        topo, wl, cfg, routing="ecmp", seed=3))
    job = int(res_x.job_finish_ticks[0])
    report(clock, dev, "table1_golden_xla", wall, lanes=1,
           ticks=cfg.n_ticks, ecmp_base_job_finish=job,
           cpu_golden=GOLDEN_JOB["ecmp_base"],
           gap_ticks=job - GOLDEN_JOB["ecmp_base"])
    res_k, wall = timed(clock, lambda: simulate(
        topo, wl, cfg._replace(**KERNEL), routing="ecmp", seed=3))
    check(all_finite((res_x, res_k)), "table1 golden: non-finite output")
    bad = int_mismatches(res_x, res_k, INT_FIELDS)
    report(clock, dev, "table1_golden_kernel", wall, lanes=1,
           ticks=cfg.n_ticks,
           ecmp_base_job_finish=int(res_k.job_finish_ticks[0]),
           int_equal_to_xla=not bad)
    check(job < cfg.n_ticks, "table1 golden: the job did not finish")
    check(not bad, f"table1 golden: kernel integers differ from XLA in "
                   f"{bad}")


def multipod_windows(clock, dev, seed, window_ticks=WINDOW_TICKS,
                     n_windows=N_WINDOWS):
    """Phase 4: 512-host windowed control loop on both backends."""
    from benchmarks.common import build_scenario
    from repro.core.netsim import SimController

    built = build_scenario("fat_tree_multipod", n_hosts=512)
    runs = {}
    for name, cfg in (("xla", built.cfg),
                      ("kernel", built.cfg._replace(**KERNEL))):
        ctl = SimController(built.topo, built.wl, cfg,
                            window_ticks=window_ticks,
                            routing=built.routing, seed=seed)

        def stepped():
            obs = [ctl.step()[1] for _ in range(n_windows)]
            return ctl.state, jax.tree.map(
                lambda *xs: np.concatenate(xs),
                *[o.samples for o in obs])

        (state, samples), wall = timed(clock, stepped)
        report(clock, dev, f"multipod512_{name}_windowed", wall,
               lanes=1, ticks=n_windows * window_ticks,
               windows=n_windows, flows=built.wl.n_flows,
               links=int(built.topo.n_links))
        ctl.reset()
        def one_window():
            state, obs = ctl.step(n_ticks=n_windows * window_ticks)
            return state, obs.samples

        (one_state, one_samples), wall = timed(clock, one_window)
        check(all_finite((state, samples, one_state, one_samples)),
              f"multipod512 {name}: NaN or inf in a lane")
        bad = int_mismatches(state.engine, one_state.engine,
                             int_fields(state.engine))
        bad += int_mismatches(samples, one_samples,
                              ("ts_min_wire", "ts_max_wire", "ts_done_min",
                               "ts_alpha_max"))
        bitwise = all(np.array_equal(np.asarray(x), np.asarray(y))
                      for x, y in zip(jax.tree.leaves((state, samples)),
                                      jax.tree.leaves((one_state,
                                                       one_samples))))
        report(clock, dev, f"multipod512_{name}_oneshot", wall,
               lanes=1, ticks=n_windows * window_ticks,
               resume_equal=not bad, all_leaves_bitwise=bitwise)
        check(not bad, f"multipod512 {name}: windowed run differs from "
                       f"one window in {bad}")
        runs[name] = (state, samples)
    (sx, mx), (sk, mk) = runs["xla"], runs["kernel"]
    bad = int_mismatches(sx.engine, sk.engine, int_fields(sx.engine))
    bad += int_mismatches(mx, mk, ("ts_min_wire", "ts_max_wire",
                                   "ts_done_min"))
    print(json.dumps({"phase": "multipod512_kernel_vs_xla",
                      "int_equal": not bad, "differs_in": bad}), flush=True)
    check(not bad, f"multipod512: kernel integers differ from XLA in {bad}")


def four_chip_grid(clock, dev, seed, lanes=8,
                   horizon_mult=MULTIPOD_GRID_HORIZON_MULT):
    """``--four-chips``: the 512-host lane grid sharded over 4 chips
    against the same lanes on one chip."""
    from benchmarks.common import (build_scenario, knob_grid, run_grid,
                                   sweep_axes_for)

    built = build_scenario("fat_tree_multipod", n_hosts=512,
                           horizon_mult=horizon_mult)
    cfg = built.cfg
    cfgs = knob_grid(cfg, sweep_axes_for("fat_tree_multipod"))[:lanes]
    results = {}
    for devices in (None, 4):
        res, wall = timed(clock, lambda: run_grid(
            built.topo, built.wl, cfgs, (seed,), routing=built.routing,
            devices=devices))
        name = "one_chip" if devices is None else "four_chips"
        report(clock, dev, f"multipod512_grid_{name}", wall,
               lanes=len(cfgs), ticks=cfg.n_ticks, chips=devices or 1,
               lane_ticks_per_s=round(
                   len(cfgs) * cfg.n_ticks / max(wall - clock.seconds,
                                                 1e-9), 1))
        check(all_finite(res), f"{name}: non-finite output")
        results[name] = res
    bad = int_mismatches(results["one_chip"], results["four_chips"],
                         INT_FIELDS + ("ts_alpha_max",))
    drift = float(np.max(np.abs(
        np.asarray(results["one_chip"].ts_throughput)
        - np.asarray(results["four_chips"].ts_throughput))))
    print(json.dumps({"phase": "multipod512_grid_sharded_vs_one_chip",
                      "equal": not bad, "differs_in": bad,
                      "ts_throughput_max_abs_diff": drift}), flush=True)
    check(not bad, f"sharded grid differs from one chip in {bad}")


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
