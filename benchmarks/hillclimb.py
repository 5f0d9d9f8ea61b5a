"""§Perf hillclimbing driver for the selected cells.

Each variant re-lowers the cell with a change and reports the roofline
terms; results accumulate in hillclimb_results.json and are written up in
EXPERIMENTS.md §Perf.

  PYTHONPATH=src python -m benchmarks.hillclimb --cell mamba2 --variant ssd_bf16
  PYTHONPATH=src python -m benchmarks.hillclimb --cell nemo15 --variant zero1
  PYTHONPATH=src python -m benchmarks.hillclimb --cell ring  --variant bf16

The ``netsim`` cell hillclimbs Symphony's control knobs (tau x k, T_win)
over the Table-1 scenario through the batched grid executor: the whole
candidate grid is ONE compile of the engine (``simulate_grid``), so a
variant's cost is dominated by device time, not re-tracing.

  PYTHONPATH=src python -m benchmarks.hillclimb --cell netsim --variant tau_k
  PYTHONPATH=src python -m benchmarks.hillclimb --cell netsim --variant t_win
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
from pathlib import Path

import jax

OUT = Path(__file__).resolve().parents[1] / "hillclimb_results.json"

PEAK, HBM, ICI = 197e12, 819e9, 50e9


def _terms(res):
    return {
        "t_compute_ms": round(res["flops_per_device"] / PEAK * 1e3, 2),
        "t_memory_ms": round(res["bytes_per_device"] / HBM * 1e3, 2),
        "t_collective_ms": round(res["wire_bytes_per_device"] / ICI * 1e3, 2),
    }


def measure_cell(arch, shape, flag_fn=None, overrides=None):
    from repro import flags
    from repro.launch.dryrun import run_cell
    if flag_fn:
        flag_fn()
    try:
        res = run_cell(arch, shape, multi_pod=False, roofline=True)
        if overrides:
            # re-run with policy overrides plumbed through the roofline path
            pass
        return _terms(res) | {"compile_s": res["compile_s"]}
    finally:
        flags.set_ssd_bf16(False)


def measure_cell_overrides(arch, shape, policy_overrides, flag_fn=None):
    """Roofline measurement with policy overrides (depth-extrapolated)."""
    from repro import flags
    from repro.launch.dryrun import _measure, collective_bytes, _full_params
    from repro.launch.mesh import make_production_mesh
    from repro.configs import registry
    from repro.models import build_model
    flags.set_roofline(True)
    if flag_fn:
        flag_fn()
    try:
        mesh = make_production_mesh()
        cfg = registry.get_config(arch)
        model = build_model(cfg)
        period = getattr(model, "period", 1)
        G = cfg.num_layers // period
        ov = {"scan_layers": False, "accum": 1}
        ov.update(policy_overrides or {})
        t0 = time.time()
        _, c1 = _measure(arch, shape, mesh, ov, period)
        _, c2 = _measure(arch, shape, mesh, ov, 2 * period)

        def costs(comp):
            ca = comp.cost_analysis()
            colls = collective_bytes(comp.as_text())
            return (float(ca.get("flops", 0)),
                    float(ca.get("bytes accessed", 0)),
                    sum(d["wire"] for d in colls.values()))

        f1, b1, w1 = costs(c1)
        f2, b2, w2 = costs(c2)

        def ext(v1, v2):
            return v1 + (v2 - v1) * (G - 1) if v2 > v1 > 0 else v2 / 2 * G

        return {
            "t_compute_ms": round(ext(f1, f2) / PEAK * 1e3, 2),
            "t_memory_ms": round(ext(b1, b2) / HBM * 1e3, 2),
            "t_collective_ms": round(ext(w1, w2) / ICI * 1e3, 2),
            "compile_s": round(time.time() - t0, 1),
        }
    finally:
        flags.set_roofline(False)
        flags.set_ssd_bf16(False)


def measure_ring(dtype="float32", mode="ring", channels=4):
    """Wire bytes of the explicit-ring grad-sync train step (danube,
    16x16 mesh, manual over data)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import flags
    from repro.config import ParallelConfig, TrainConfig
    from repro.configs import registry
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.models.params import abstract_tree
    from repro.optim.adamw import OptState
    from repro.parallel.sharding import make_rules
    from repro.runtime.train import make_train_step

    flags.set_ring_sync_dtype(dtype)
    try:
        mesh = make_production_mesh()
        cfg = registry.get_config("h2o_danube_3_4b")
        par = ParallelConfig(grad_sync=mode, ring_buckets=channels,
                             remat="block", scan_layers=True)
        rules = make_rules()
        model = build_model(cfg, par, mesh=mesh, rules=rules)
        tcfg = TrainConfig(global_batch=256, seq_len=4096)
        step = make_train_step(model, cfg, tcfg, par, mesh)
        p_abs = model.abstract_params()
        spec_tree = model.param_spec()
        f32 = abstract_tree(spec_tree, rules, mesh)
        recast = lambda t, d: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, d, sharding=x.sharding), t)
        opt = OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       m=recast(f32, jnp.float32), v=recast(f32, jnp.float32),
                       master=recast(f32, jnp.float32))
        from jax.sharding import NamedSharding
        tok = jax.ShapeDtypeStruct((256, 4096), jnp.int32,
                                   sharding=NamedSharding(mesh, P("data", None)))
        batch = {"tokens": tok, "labels": tok}
        t0 = time.time()
        with mesh:
            compiled = step.lower(p_abs, opt, batch).compile()
        colls = collective_bytes(compiled.as_text())
        wire = sum(d["wire"] for d in colls.values())
        return {
            "wire_gb_per_device": round(wire / 1e9, 3),
            "t_collective_ms": round(wire / ICI * 1e3, 2),
            "collectives": {k: {"count": v["count"],
                                "wire_gb": round(v["wire"] / 1e9, 3)}
                            for k, v in colls.items()},
            "compile_s": round(time.time() - t0, 1),
        }
    finally:
        flags.set_ring_sync_dtype("float32")


def measure_netsim_grid(axes: dict, seeds=4, devices="env"):
    """Hillclimb Symphony knobs on the Table-1 scenario via simulate_grid.

    Returns the best grid point by median CCT plus the grid's wall time
    and engine compile count (must be 1: the grid is a single program).
    ``devices`` shards the candidate lanes across a device mesh (default
    defers to BENCH_DEVICES; this module forces 512 virtual CPU devices,
    so ``devices="auto"`` spreads the grid wide).
    """
    import numpy as np
    from benchmarks.common import (build_scenario, grid_devices, knob_combos,
                                   knob_grid, run_grid)
    from repro.core.netsim import (core_trace_count, metrics,
                                   resolve_grid_mesh)

    topo, wl, base, routing = build_scenario("table1_ring", passes=2)
    cfgs = knob_grid(base._replace(sym_on=True), axes)
    mesh = resolve_grid_mesh(
        devices=grid_devices() if devices == "env" else devices)
    c0 = core_trace_count()
    t0 = time.time()
    res = run_grid(topo, wl, cfgs, list(range(seeds)), routing,
                   devices=devices)
    wall = time.time() - t0
    compiles = core_trace_count() - c0
    cct = metrics.cct_seconds(res, wl, base)[..., 0]      # [K, S]
    med = np.nanmedian(cct, axis=1)
    order = np.argsort(np.where(np.isfinite(med), med, np.inf))
    best = int(order[0])
    axis_names = list(axes)
    combos = knob_combos(axes)    # same row-major order as knob_grid
    return {
        "grid_points": len(cfgs), "seeds": seeds,
        "device_count": 1 if mesh is None else int(mesh.devices.size),
        "grid_wall_s": round(wall, 1), "engine_compiles": compiles,
        "best": dict(zip(axis_names, combos[best])) |
                {"cct_median_s": round(float(med[best]), 4)},
        "cct_median_by_point": {
            "/".join(f"{v:g}" for v in combos[i]): round(float(med[i]), 4)
            for i in order[:8] if np.isfinite(med[i])},
    }


def measure_netsim_online(window_recs: int = 8, max_windows: int = 600,
                          seed: int = 0):
    """Online tuner over the ``step()`` control plane: retune tau/k every
    window against the live per-window observations (alternating-coordinate
    hillclimb on aggregate delivered throughput), next to the offline grid
    cell.  The windowed engine compiles ONCE; every retune is a free knob
    update (``engine_compiles`` must be 1 across ALL windows of BOTH the
    tuned and the fixed-knob rollout)."""
    import numpy as np
    from benchmarks.common import build_scenario
    from repro.core.netsim import SimController, core_trace_count
    from repro.core.netsim.simulator import I32MAX

    topo, wl, base, routing = build_scenario("table1_ring", passes=2)
    cfg = base._replace(sym_on=True)
    window = cfg.record_every * window_recs

    def rollout(policy):
        ctl = SimController(topo, wl, cfg, window_ticks=window,
                            routing=routing, seed=seed)
        action, obs = None, None
        for i in range(max_windows):
            _, obs = ctl.step(action)
            if obs.done:
                break
            action = policy(i, obs) if policy else None
        jf = np.asarray(ctl.state.engine.job_finish)
        cct = float(jf[0]) * cfg.dt if jf[0] != I32MAX else None
        return ctl, obs, cct, i + 1

    knobs = {"tau": 0.25, "k": 0.01}
    bounds = {"tau": (0.02, 0.8), "k": (1e-4, 0.3)}
    factor = {"tau": 1.5, "k": 2.0}
    direction = {"tau": -1, "k": 1}
    prev_obj = -np.inf
    trace = []

    def tuner(i, obs):
        nonlocal prev_obj
        obj = float(np.sum(obs.stats.tput))
        name = "tau" if i % 2 == 0 else "k"
        if obj < prev_obj:          # last move hurt: reverse that coordinate
            direction[name] *= -1
        prev_obj = obj
        lo, hi = bounds[name]
        knobs[name] = float(np.clip(
            knobs[name] * factor[name] ** direction[name], lo, hi))
        trace.append({"window": i, "tput_sum": round(obj / 1e9, 3),
                      "alpha_max": round(obs.stats.alpha_max, 1),
                      **{k: round(v, 4) for k, v in knobs.items()}})
        return dict(knobs)

    c0 = core_trace_count()
    t0 = time.time()
    _, _, cct_online, w_online = rollout(tuner)
    _, _, cct_fixed, w_fixed = rollout(None)
    wall = time.time() - t0
    compiles = core_trace_count() - c0
    return {
        "window_ticks": window,
        "windows_online": w_online, "windows_fixed": w_fixed,
        "engine_compiles": compiles,
        "wall_s": round(wall, 1),
        "final_knobs": {k: round(v, 4) for k, v in knobs.items()},
        "cct_online_s": round(cct_online, 4) if cct_online else None,
        "cct_fixed_s": round(cct_fixed, 4) if cct_fixed else None,
        "online_vs_fixed": round(cct_fixed / cct_online, 3)
        if cct_online and cct_fixed else None,
        "tuner_trace_head": trace[:6],
    }


VARIANTS = {
    ("mamba2", "baseline"): lambda: measure_cell("mamba2_130m", "train_4k"),
    ("mamba2", "ssd_bf16"): lambda: measure_cell(
        "mamba2_130m", "train_4k",
        flag_fn=lambda: __import__("repro.flags", fromlist=["x"]).set_ssd_bf16(True)),
    ("nemo15", "baseline"): lambda: measure_cell_overrides(
        "nemotron_4_15b", "train_4k", {}),
    ("nemo15", "zero1"): lambda: measure_cell_overrides(
        "nemotron_4_15b", "train_4k", {"fsdp": False, "zero1": True}),
    ("ring", "f32"): lambda: measure_ring("float32"),
    ("ring", "bf16"): lambda: measure_ring("bfloat16"),
    ("ring", "psum"): lambda: measure_ring("float32", mode="xla"),
    ("ring", "bf16_c8"): lambda: measure_ring("bfloat16", channels=8),
    ("netsim", "tau_k"): lambda: measure_netsim_grid(
        {"tau": (0.1, 0.2, 0.25, 0.4, 0.5), "k": (1e-3, 3e-3, 1e-2, 3e-2)}),
    ("netsim", "t_win"): lambda: measure_netsim_grid(
        {"t_win_ticks": (5, 10, 20, 40), "k": (3e-3, 1e-2)}),
    ("netsim", "red"): lambda: measure_netsim_grid(
        {"red_pmax": (0.1, 0.2, 0.4), "red_kmin": (25e3, 50e3, 75e3)}),
    ("netsim", "online"): lambda: measure_netsim_online(),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--variant", required=True)
    args = ap.parse_args()
    from benchmarks.common import enable_compile_cache
    enable_compile_cache()
    res = VARIANTS[(args.cell, args.variant)]()
    data = json.loads(OUT.read_text()) if OUT.exists() else {}
    data[f"{args.cell}/{args.variant}"] = res
    OUT.write_text(json.dumps(data, indent=1))
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
