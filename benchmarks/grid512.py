"""The 512-host multi-pod sweep artifact — ``BENCH_grid512.json``.

Runs the registry-driven Table-2/Fig-8-style knob sweep (sym x tau x k x
T_win, x seeds) on the 3-tier multi-pod FatTree at 128/256/512 hosts
through the sharded grid executor (``simulate_grid(devices="auto")``),
and measures lane-scaling efficiency: lanes/sec per device and the
1 -> N-device grid speedup on the same program.

The committed artifact tracks two things across PRs:

* the sweep itself (best Symphony operating point + improvement per host
  count) — the paper's dense evaluation grid, at Swing/DS-Sync scale;
* the scaling numbers — whether the flattened ``K*S`` lane axis actually
  spreads across devices.  On a single-core CI/dev host the forced
  8-device CPU mesh buys nothing (all shards serialize on one core, so
  ``speedup_1_to_n`` honestly reports ~1.0 or below, exactly like the
  committed ``grid_speedup_vs_per_point = 0.87``); on multi-core or
  accelerator hosts the same artifact records real scaling.

Regenerate with::

    PYTHONPATH=src python -m benchmarks.grid512            # quick mode
    BENCH_QUICK=0 PYTHONPATH=src python -m benchmarks.grid512   # full

Run as a script it forces ``--xla_force_host_platform_device_count=8``
on CPU hosts (set ``XLA_FLAGS`` yourself to override) so the sharded
path is exercised even without accelerators.
"""
import os

if "XLA_FLAGS" not in os.environ:  # must precede the first jax import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import json
import platform
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core.netsim import core_trace_count, metrics, resolve_grid_mesh

from .common import (QUICK, build_scenario, enable_compile_cache, knob_combos,
                     knob_grid, run_grid, sweep_axes_for)

BENCH_FILE = Path(__file__).resolve().parents[1] / "BENCH_grid512.json"
BENCH_SCHEMA = 1

SCENARIO = "fat_tree_multipod"

# single source of truth for the artifact parameters.  Quick mode is what
# a 1-core host can regenerate in ~half an hour: small per-step chunks,
# ring-of-8 stripes, 2.5x horizons.  Full mode is the paper-faithful grid
# (ring 32, 8 MB chunks, the full tau x k x T_win axes) for real hardware.
CONFIG = dict(
    hosts=(128, 256, 512),
    ring=8 if QUICK else 32,
    chunk=512e3 if QUICK else 8e6,
    # ECMP collisions on the 1:2-oversubscribed core stretch the CCT
    # tail to ~5.2x the lockstep ideal at 128 hosts; 7x keeps every
    # lane finishing across seeds and Symphony on/off
    horizon_mult=7.0,
    n_seeds=1 if QUICK else 2,
    scaling_ticks=64 if QUICK else 256,
    scaling_lanes=8,
)


def _mesh_info():
    mesh = resolve_grid_mesh(devices="auto")
    n = 1 if mesh is None else int(mesh.devices.size)
    return n, [n]


def _pair_gains(cfgs, axes, med):
    """Pair each sym=True grid point with its sym=False twin (same values
    on every other axis) and report the best Symphony improvement."""
    names = list(axes)
    combos = knob_combos(axes)          # row-major, same order as knob_grid
    if "sym" not in names:
        return None
    si = names.index("sym")
    base = {tuple(c[:si] + c[si + 1:]): i
            for i, c in enumerate(combos) if not c[si]}
    best = None
    for i, c in enumerate(combos):
        if not c[si]:
            continue
        j = base.get(tuple(c[:si] + c[si + 1:]))
        if j is None or not (np.isfinite(med[i]) and np.isfinite(med[j])):
            continue
        gain = float(1 - med[i] / med[j])
        if best is None or gain > best["improvement"]:
            best = {"improvement": round(gain, 4),
                    "baseline_cct_s": round(float(med[j]), 4),
                    "symphony_cct_s": round(float(med[i]), 4)}
            best.update({n: v for n, v in zip(names, combos[i])
                         if n != "sym"})
    return best


def sweep_at(n_hosts: int) -> dict:
    """The registry sweep at one host count, sharded over all devices."""
    axes = sweep_axes_for(SCENARIO)
    built = build_scenario(SCENARIO, n_hosts=n_hosts, ring=CONFIG["ring"],
                           chunk=CONFIG["chunk"],
                           horizon_mult=CONFIG["horizon_mult"])
    cfgs = knob_grid(built.cfg, axes)
    seeds = list(range(CONFIG["n_seeds"]))
    lanes = len(cfgs) * len(seeds)
    n_dev, mesh_shape = _mesh_info()
    c0 = core_trace_count()
    t0 = time.time()
    res = run_grid(built.topo, built.wl, cfgs, seeds, built.routing,
                   devices="auto")
    wall = time.time() - t0
    compiles = core_trace_count() - c0
    cct = metrics.cct_seconds(res, built.wl, built.cfg)[..., 0]   # [K, S]
    med = np.nanmedian(cct, axis=1)
    lane_ticks = lanes * built.cfg.n_ticks
    return {
        "n_hosts": n_hosts,
        "n_links": built.topo.n_links,
        "n_ticks": built.cfg.n_ticks,
        "grid_points": len(cfgs),
        "seeds": len(seeds),
        "lanes": lanes,
        "devices": n_dev,
        "mesh_shape": mesh_shape,
        "grid_compiles": compiles,
        "wall_s": round(wall, 1),
        "lanes_per_s": round(lanes / wall, 4),
        "lane_ticks_per_s": round(lane_ticks / wall, 1),
        "lane_ticks_per_s_per_device": round(lane_ticks / wall / n_dev, 1),
        "unfinished_lanes": int(np.isnan(cct).sum()),
        "best_symphony": _pair_gains(cfgs, axes, med),
    }


def scaling_at(n_hosts: int) -> dict:
    """1 -> N-device lane-scaling on a short fixed-tick grid: the same
    compiled program dispatched unsharded, then sharded over all local
    devices."""
    built = build_scenario(SCENARIO, n_hosts=n_hosts, ring=CONFIG["ring"],
                           chunk=CONFIG["chunk"])
    n_ticks = CONFIG["scaling_ticks"]
    lanes = CONFIG["scaling_lanes"]
    base = built.cfg._replace(n_ticks=n_ticks, sym_on=True)
    cfgs = knob_grid(base, {"tau": tuple(
        np.round(np.linspace(0.1, 0.5, lanes), 3).tolist())})
    n_dev, _ = _mesh_info()

    def timed(devices):
        # warm-up dispatch compiles; the second dispatch is the measurement
        run_grid(built.topo, built.wl, cfgs, [0], built.routing,
                 devices=devices)
        t0 = time.time()
        run_grid(built.topo, built.wl, cfgs, [0], built.routing,
                 devices=devices)
        return time.time() - t0

    wall_1 = timed(1)
    wall_n = timed("auto") if n_dev > 1 else wall_1
    lane_ticks = lanes * n_ticks
    return {
        "n_hosts": n_hosts,
        "n_ticks": n_ticks,
        "lanes": lanes,
        "devices": n_dev,
        "wall_1dev_s": round(wall_1, 2),
        "wall_ndev_s": round(wall_n, 2),
        "speedup_1_to_n": round(wall_1 / wall_n, 2),
        "lane_ticks_per_s_1dev": round(lane_ticks / wall_1, 1),
        "lane_ticks_per_s_ndev": round(lane_ticks / wall_n, 1),
        "lane_ticks_per_s_per_device_ndev": round(
            lane_ticks / wall_n / n_dev, 1),
    }


def run() -> dict:
    out = {"sweep": {}, "scaling": {}}
    for h in CONFIG["hosts"]:
        out["scaling"][f"hosts_{h}"] = scaling_at(h)
        print(f"scaling @ {h} hosts:",
              json.dumps(out["scaling"][f"hosts_{h}"]), flush=True)
        out["sweep"][f"hosts_{h}"] = sweep_at(h)
        print(f"sweep @ {h} hosts:",
              json.dumps(out["sweep"][f"hosts_{h}"]), flush=True)
    return out


def _mode() -> str:
    return "quick" if QUICK else "full"


def write_bench(result) -> dict:
    data = {}
    if BENCH_FILE.exists():
        data = json.loads(BENCH_FILE.read_text())
        if data.get("schema") != BENCH_SCHEMA:
            data = {}
    data["schema"] = BENCH_SCHEMA
    n_dev, mesh_shape = _mesh_info()
    data[_mode()] = {
        "config": {k: list(v) if isinstance(v, tuple) else v
                   for k, v in CONFIG.items()},
        "host": {"cpu_count": os.cpu_count(),
                 "machine": platform.machine(),
                 "jax": jax.__version__,
                 "jax_backend": jax.default_backend(),
                 "device_count": jax.device_count(),
                 "mesh_shape": mesh_shape},
        "result": result,
    }
    BENCH_FILE.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
    return data


def main(argv) -> int:
    enable_compile_cache()
    t0 = time.time()
    res = run()
    res["_wall_s"] = round(time.time() - t0, 1)
    write_bench(res)
    print(json.dumps(res, indent=1))
    print(f"wrote {BENCH_FILE}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
