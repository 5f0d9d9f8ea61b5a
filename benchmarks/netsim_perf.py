"""Simulator performance benchmark — the §Perf record for the netsim layer.

Measures, on the Table-1 scenario:

* single-run / multi-seed ticks-per-second (as before);
* **grid dispatch**: a Fig.-8-style 16-point knob grid (tau x k, Symphony
  on) through ``simulate_grid`` — one compile, vmapped — versus *per-point
  dispatch*, where every grid point pays its own trace+compile the way the
  pre-split engine (all of SimParams in ``static_argnames``) did.  Both
  end-to-end wall clock and the compile-only ratio are reported: the
  split converts O(grid) trace+compiles into O(1), so
  ``compile_speedup_vs_per_point`` scales with grid size (>= 5x from ~8
  points up).  End-to-end speedup additionally depends on how well the
  host vectorizes the batched lanes (on a 1-2 core CPU the batched and
  sequential executions run at similar throughput; on parallel backends
  the grid wins on both axes);
* **compile count**: ``core_trace_count()`` across the grid must be
  exactly 1 — the CI smoke job asserts this, so an accidental re-trace in
  the grid executor fails the build.

Under BENCH_QUICK the per-point reference is sampled on a subset of the
grid and extrapolated (compiles dominate it, so this is conservative).

The result also carries an xla-vs-pallas tick-backend comparison and is
persisted as ``BENCH_netsim.json`` at the repo root — the tracked perf
artifact.  ``python -m benchmarks.netsim_perf`` refreshes it;
``python -m benchmarks.netsim_perf --check`` re-measures and compares
against the committed numbers (warn-only: CI hosts are 2-core shared
VMs, so throughput is gated loosely and never fails the build).
"""
import functools
import json
import os
import platform
import sys
import time
from pathlib import Path

import jax

from repro.core.netsim import (core_trace_count, grid_from_params,
                               resolve_grid_mesh, simulate, simulate_grid,
                               simulate_seeds)
from repro.core.netsim.simulator import (_core_impl, _resolve_routing,
                                         build_static, wl_arrays)

from .common import (QUICK, build_scenario, cached, default_params,
                     enable_compile_cache, kernel_tuning, knob_grid)

BENCH_FILE = Path(__file__).resolve().parents[1] / "BENCH_netsim.json"
# Schema 3: adds the append-only "trajectory" list — one entry per PR
# (git sha + kernel configuration + ticks/sec), the longitudinal perf
# record the per-mode snapshot entries cannot provide.
BENCH_SCHEMA = 3

# The gather-free tiled kernel configuration: packed per-block route
# tables streamed via BlockSpec + scalar prefetch remove every gather
# AND scatter from the tiled onehot lowering (the Mosaic-ready shape).
# Benchmarked as its own trajectory variant alongside the tuned window.
GATHERFREE_TUNING = {"segsum": "onehot", "blk": 256, "tick_window": 1}

# single source of truth for the benchmark parameters and the cache key
CONFIG = dict(n_ticks=2_000 if QUICK else 30_000,
              taus=(0.1, 0.2, 0.25, 0.5), ks=(1e-3, 3e-3, 1e-2, 3e-2),
              n_seeds=4 if QUICK else 8,
              grid_seeds=1 if QUICK else 2,
              backends=("xla", "pallas"),
              tuning=kernel_tuning(),
              gatherfree=GATHERFREE_TUNING,
              windows=8 if QUICK else 16)


def _git_sha() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=BENCH_FILE.parent, capture_output=True, text=True,
            timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _per_point_reference(topo, wl, cfgs, seed=0):
    """Legacy dispatch: a fresh jit per grid point, as when every SimParams
    field was a static argument — each point re-traces and re-compiles.

    Returns (total_wall_s, total_compile_s): the compile term is measured
    separately via AOT lower+compile of the same fresh program.
    """
    wall = comp = 0.0
    for cfg in cfgs:
        cfg_r, mode = _resolve_routing(cfg, "ecmp")
        st = build_static(topo, wl, mode, seed, dt=cfg_r.dt,
                          deploy=cfg_r.deploy)
        struct, knobs = cfg_r.split()
        wla = wl_arrays(wl, struct.dt)
        key = jax.random.PRNGKey(seed)
        fresh = jax.jit(functools.partial(_core_impl),
                        static_argnames=("struct",))
        t0 = time.time()
        compiled = fresh.lower(st, wla, struct=struct, knobs=knobs,
                               key=key).compile()
        comp += time.time() - t0
        t0 = time.time()
        jax.block_until_ready(compiled(st, wla, knobs=knobs, key=key))
        wall += time.time() - t0
    return wall + comp, comp


def backend_compare(topo, wl, cfg):
    """Warm-run ticks/sec for the staged XLA tick vs the fused Pallas
    kernel (``kernels/netsim_tick``).  On the CPU CI host the kernel runs
    in interpret mode — it traces into the same XLA program, so parity
    (~1.0x) is the expected result there; the fusion win is a memory-
    traffic story on real accelerators (see ``benchmarks/roofline.py``'s
    ``netsim_tick`` section for the analytic bytes-moved model)."""
    from repro.kernels.netsim_tick import use_interpret
    n_ticks = cfg.n_ticks
    tuning = CONFIG["tuning"]
    variants = [("xla", cfg._replace(backend="xla")),
                ("pallas", cfg._replace(backend="pallas")),
                # the trajectory configuration: the fused kernel with the
                # multi-tick window (and any BENCH_SEGSUM/BENCH_BLK
                # overrides) — what BENCH_netsim.json tracks across PRs
                ("pallas_tuned", cfg._replace(backend="pallas", **tuning)),
                # the gather-free Mosaic-ready tiled configuration — the
                # second tracked trajectory variant
                ("pallas_gatherfree",
                 cfg._replace(backend="pallas", **GATHERFREE_TUNING))]
    out = {}
    for be, c in variants:
        t0 = time.time()
        jax.block_until_ready(simulate(topo, wl, c, "ecmp", 0))
        cold = time.time() - t0
        t0 = time.time()
        jax.block_until_ready(simulate(topo, wl, c, "ecmp", 1))
        warm = time.time() - t0
        out[be] = {
            "compile_plus_run_s": round(cold, 2),
            "single_run_s": round(warm, 3),
            "ticks_per_s": round(n_ticks / warm),
        }
    out["pallas_interpret"] = use_interpret()
    out["pallas_vs_xla"] = round(
        out["pallas"]["ticks_per_s"] / out["xla"]["ticks_per_s"], 2)
    out["pallas_tuned_vs_xla"] = round(
        out["pallas_tuned"]["ticks_per_s"] / out["xla"]["ticks_per_s"], 2)
    out["pallas_gatherfree_vs_xla"] = round(
        out["pallas_gatherfree"]["ticks_per_s"] / out["xla"]["ticks_per_s"],
        2)
    return out


def measure_windowed(topo, wl, cfg):
    """``step_overhead``: per-window dispatch cost of the online control
    plane.  The same tick horizon is run once as a closed scan and once as
    ``windows`` sequential ``run_window`` dispatches (the ``step()`` path,
    one host round-trip per window), both warm.  ``step_overhead`` is the
    windowed/one-shot wall ratio — the price of being resumable/retunable
    every window; ``per_window_dispatch_ms`` is the same cost per window.
    """
    from repro.core.netsim import init_state, run_window
    cfg_r, mode = _resolve_routing(cfg, "ecmp")
    struct, knobs = cfg_r.split()
    st = build_static(topo, wl, mode, 0, dt=struct.dt, deploy=struct.deploy)
    wla = wl_arrays(wl, struct.dt)
    R = struct.record_every
    n_win = CONFIG["windows"]
    win = max(R, cfg.n_ticks // n_win // R * R)
    total = win * n_win

    cfg_t = cfg._replace(n_ticks=total)
    jax.block_until_ready(simulate(topo, wl, cfg_t, "ecmp", 0))   # compile
    t0 = time.time()
    jax.block_until_ready(simulate(topo, wl, cfg_t, "ecmp", 1))
    oneshot = time.time() - t0

    key = jax.random.PRNGKey(0)
    state = init_state(st, wla, struct, key)
    jax.block_until_ready(
        run_window(st, wla, struct, knobs, state, win)[0])        # compile
    state = init_state(st, wla, struct, key)
    t0 = time.time()
    for _ in range(n_win):
        state, _ = run_window(st, wla, struct, knobs, state, win)
    jax.block_until_ready(state)
    windowed = time.time() - t0
    return {
        "window_ticks": win,
        "n_windows": n_win,
        "total_ticks": total,
        "oneshot_s": round(oneshot, 3),
        "windowed_s": round(windowed, 3),
        "ticks_per_s": round(total / windowed),
        "step_overhead": round(windowed / oneshot, 3),
        "per_window_dispatch_ms": round(
            max(windowed - oneshot, 0.0) / n_win * 1e3, 3),
    }


def run():
    topo, wl, _, _ = build_scenario("table1_ring", passes=2)
    n_ticks = CONFIG["n_ticks"]
    cfg = default_params(n_ticks, sym=True)

    t0 = time.time()
    jax.block_until_ready(simulate(topo, wl, cfg, "ecmp", 0))
    cold = time.time() - t0
    t0 = time.time()
    jax.block_until_ready(simulate(topo, wl, cfg, "ecmp", 1))
    warm = time.time() - t0

    seeds = list(range(CONFIG["n_seeds"]))
    t0 = time.time()
    jax.block_until_ready(simulate_seeds(topo, wl, cfg, "ecmp", seeds))
    batch = time.time() - t0

    # ---- Fig.-8-style knob grid: 16 points (4 tau x 4 k)
    cfgs = knob_grid(cfg, {"tau": CONFIG["taus"], "k": CONFIG["ks"]})
    struct, knobs = grid_from_params(cfgs)
    grid_seeds = list(range(CONFIG["grid_seeds"]))
    c0 = core_trace_count()
    t0 = time.time()
    jax.block_until_ready(
        simulate_grid(topo, wl, struct, knobs, grid_seeds, routing="ecmp",
                      chunk_knobs=8))
    grid_wall = time.time() - t0
    grid_compiles = core_trace_count() - c0
    # compile-only cost of the grid program, measured the same way as the
    # per-point reference: AOT trace+compile of a fresh jit of the body
    from repro.core.netsim.simulator import (_grid_impl, _stacked_statics)
    struct_r, mode = _resolve_routing(struct, "ecmp")
    st_stack, keys = _stacked_statics(topo, wl, mode, grid_seeds, struct_r)
    kn8 = jax.tree.map(lambda x: x[:8], knobs)
    fresh_grid = jax.jit(functools.partial(_grid_impl),
                         static_argnames=("struct",))
    t0 = time.time()
    fresh_grid.lower(st_stack, wl_arrays(wl, struct_r.dt), struct=struct_r,
                     knobs_stack=kn8, keys=keys).compile()
    grid_compile_s = time.time() - t0

    ref_cfgs = cfgs[:4] if QUICK else cfgs
    pp_total, pp_comp = _per_point_reference(topo, wl, ref_cfgs)
    # honest legacy model: seeds were traced even pre-split, so per-point
    # dispatch pays K compiles but K*S runs
    scale_k = len(cfgs) / len(ref_cfgs)
    pp_run = pp_total - pp_comp
    pp_comp *= scale_k
    pp_wall = pp_comp + pp_run * scale_k * len(grid_seeds)
    backends = backend_compare(topo, wl, cfg)

    # ---- multi-device grid dispatch: the same grid sharded across all
    # local devices (only measurable when >1 device is visible — force a
    # CPU mesh with XLA_FLAGS=--xla_force_host_platform_device_count=8).
    # Both walls include their single compile, so the ratio is honest.
    lanes = len(cfgs) * len(grid_seeds)
    mesh = resolve_grid_mesh(devices="auto")
    n_dev = 1 if mesh is None else int(mesh.devices.size)
    multi = {"grid_devices": n_dev}
    if mesh is not None:
        t0 = time.time()
        jax.block_until_ready(
            simulate_grid(topo, wl, struct, knobs, grid_seeds,
                          routing="ecmp", chunk_knobs=8, devices="auto"))
        multi_wall = time.time() - t0
        multi.update({
            "grid_multi_wall_s": round(multi_wall, 2),
            "grid_speedup_multi_device": round(grid_wall / multi_wall, 2),
            "ticks_per_s_grid_per_device_multi": round(
                lanes * n_ticks / multi_wall / n_dev),
        })
    windowed = measure_windowed(topo, wl, cfg)

    return {
        "backends": backends,
        "windowed": windowed,
        "compile_plus_run_s": round(cold, 2),
        "single_run_s": round(warm, 2),
        "ticks_per_s_single": round(n_ticks / warm),
        "vmap_seeds": len(seeds),
        "vmap_runs_s": round(batch, 2),
        "ticks_per_s_vmap": round(len(seeds) * n_ticks / batch),
        "vmap_speedup": round(len(seeds) * warm / batch, 2),
        "grid_points": len(cfgs),
        "grid_seeds": len(grid_seeds),
        "grid_lanes": lanes,
        "grid_wall_s": round(grid_wall, 2),
        "grid_compiles": grid_compiles,
        # each lane advances n_ticks in grid_wall seconds; "total" is the
        # aggregate simulation throughput of the whole grid dispatch
        "ticks_per_s_grid_lane": round(n_ticks / grid_wall, 1),
        "ticks_per_s_grid_total": round(lanes * n_ticks / grid_wall),
        "ticks_per_s_grid_per_device": round(lanes * n_ticks / grid_wall),
        "per_point_wall_s": round(pp_wall, 2),
        "per_point_compile_s": round(pp_comp, 2),
        "per_point_extrapolated": len(ref_cfgs) != len(cfgs),
        "grid_speedup_vs_per_point": round(pp_wall / grid_wall, 2),
        "compile_speedup_vs_per_point": round(
            pp_comp / max(grid_compile_s, 1e-9), 2),
        **multi,
    }


def bench():
    return cached("netsim_perf", run, config=CONFIG)


# --------------------------------------------- BENCH_netsim.json artifact
def _mode() -> str:
    return "quick" if QUICK else "full"


def write_bench(result) -> dict:
    """Merge this run into the committed perf artifact, keyed by mode
    ("quick" = the CI configuration, "full" = the local 30k-tick one),
    and append this commit's entry to the per-PR ``trajectory`` list."""
    data = {}
    if BENCH_FILE.exists():
        data = json.loads(BENCH_FILE.read_text())
        if data.get("schema") == 2:
            # schema 2 -> 3: mode snapshot entries carry over unchanged;
            # the trajectory starts empty and grows from this run on.
            data["schema"] = BENCH_SCHEMA
        elif data.get("schema") != BENCH_SCHEMA:
            data = {}
    data["schema"] = BENCH_SCHEMA
    mesh = resolve_grid_mesh(devices="auto")
    n_dev = 1 if mesh is None else int(mesh.devices.size)
    data[_mode()] = {
        "config": {k: list(v) if isinstance(v, tuple) else v
                   for k, v in CONFIG.items()},
        # device_count/mesh_shape make BENCH entries from different
        # topologies (1-device CI VM vs forced-8 CPU mesh vs accelerator
        # pods) comparable instead of silently conflated
        "host": {"cpu_count": os.cpu_count(),
                 "machine": platform.machine(),
                 "jax": jax.__version__,
                 "jax_backend": jax.default_backend(),
                 "device_count": jax.device_count(),
                 "mesh_shape": [n_dev]},
        "result": result,
    }
    # ---- append-only per-PR trajectory, one entry per kernel variant
    # (re-running on the same commit, mode, and variant updates that
    # entry in place instead of duplicating it; entries from before the
    # variant field carried the tuned configuration, so missing variant
    # reads as "pallas_tuned")
    sha = _git_sha()
    traj = data.get("trajectory", [])
    for variant, tuning in (("pallas_tuned", CONFIG["tuning"]),
                            ("pallas_gatherfree", GATHERFREE_TUNING),
                            ("windowed", None)):
        if variant == "windowed":
            # the online-control-plane dispatch path: W run_window calls
            # over the closed scan's horizon (xla backend) — tracks the
            # per-window resume/retune cost across PRs.  Absent from
            # partial results (e.g. the dedupe test's fixture): skip.
            w = result.get("windowed")
            if w is None:
                continue
            entry = {
                "sha": sha,
                "mode": _mode(),
                "variant": variant,
                "backend": "xla",
                "segsum": None, "blk": None, "tick_window": None,
                "window_ticks": w["window_ticks"],
                "n_windows": w["n_windows"],
                "ticks_per_s": w["ticks_per_s"],
                "step_overhead": w["step_overhead"],
                "ticks_per_s_xla": result["backends"]["xla"]["ticks_per_s"],
                "device_count": jax.device_count(),
            }
        else:
            entry = {
                "sha": sha,
                "mode": _mode(),
                "variant": variant,
                "backend": "pallas",
                "segsum": tuning["segsum"],
                "blk": tuning["blk"],
                "tick_window": tuning["tick_window"],
                "lanes": result.get("grid_lanes"),
                "ticks_per_s": result["backends"][variant]["ticks_per_s"],
                "ticks_per_s_xla": result["backends"]["xla"]["ticks_per_s"],
                "device_count": jax.device_count(),
            }
        traj = [e for e in traj
                if not (e.get("sha") == entry["sha"]
                        and e.get("mode") == entry["mode"]
                        and e.get("variant", "pallas_tuned")
                        == entry["variant"])]
        traj.append(entry)
    data["trajectory"] = traj
    BENCH_FILE.write_text(json.dumps(data, indent=1, sort_keys=True) + "\n")
    return data


# Ticks/sec metrics gated by --check, as (path into the result dict).
# grid_speedup_multi_device only exists when >1 device was visible for
# BOTH the committed and the fresh run; the check loop skips it otherwise.
_GATED = (("ticks_per_s_single",), ("ticks_per_s_vmap",),
          ("backends", "xla", "ticks_per_s"),
          ("backends", "pallas", "ticks_per_s"),
          ("backends", "pallas_tuned", "ticks_per_s"),
          ("backends", "pallas_gatherfree", "ticks_per_s"),
          ("windowed", "ticks_per_s"),
          ("grid_speedup_multi_device",))
# Warn below 0.5x committed: CI runs on shared 2-core VMs whose absolute
# throughput swings widely run-to-run, so the gate is loose and warn-only —
# it catches order-of-magnitude regressions, not percent-level ones.
CHECK_RATIO = 0.5


def check() -> int:
    """Warn-only regression gate against the committed BENCH_netsim.json."""
    if not BENCH_FILE.exists():
        print(f"netsim_perf --check: no {BENCH_FILE.name}; skipping")
        return 0
    data = json.loads(BENCH_FILE.read_text())
    entry = data.get(_mode())
    if data.get("schema") != BENCH_SCHEMA or entry is None:
        print(f"netsim_perf --check: no committed '{_mode()}' entry "
              f"(schema {data.get('schema')}); skipping")
        return 0
    committed, fresh = entry["result"], run()
    warned = False
    for path in _GATED:
        want, have = committed, fresh
        try:
            for k in path:
                want, have = want[k], have[k]
        except KeyError:
            continue
        if not all(isinstance(v, (int, float)) for v in (want, have)) \
                or want <= 0:
            continue
        label = ".".join(path)
        line = (f"  {label}: {have} vs committed {want} "
                f"({have / want:.2f}x)")
        if have < CHECK_RATIO * want:
            # ::warning:: renders as a GitHub Actions annotation
            print(f"::warning title=netsim_perf regression::{label} "
                  f"{have} < {CHECK_RATIO} * committed {want}")
            warned = True
        print(line)
    # ---- trajectory gate: fresh fused-kernel throughput vs the newest
    # committed trajectory entry for this mode AND variant (same
    # warn-only contract; pre-variant entries read as pallas_tuned)
    for variant in ("pallas_tuned", "pallas_gatherfree", "windowed"):
        traj = [e for e in data.get("trajectory", [])
                if e.get("mode") == _mode()
                and e.get("variant", "pallas_tuned") == variant
                and isinstance(e.get("ticks_per_s"), (int, float))]
        if not traj:
            print(f"  trajectory[{variant}]: no committed entry for mode "
                  f"'{_mode()}' yet")
            continue
        last = traj[-1]
        want = last["ticks_per_s"]
        have = (fresh["windowed"]["ticks_per_s"] if variant == "windowed"
                else fresh["backends"][variant]["ticks_per_s"])
        print(f"  trajectory[{last.get('sha')}/{variant}].ticks_per_s: "
              f"{have} vs committed {want} ({have / want:.2f}x; segsum="
              f"{last.get('segsum')} blk={last.get('blk')} "
              f"tick_window={last.get('tick_window')})")
        if want > 0 and have < CHECK_RATIO * want:
            print(f"::warning title=netsim_perf trajectory regression::"
                  f"{variant} {have} < {CHECK_RATIO} * committed {want} "
                  f"(entry {last.get('sha')})")
            warned = True
    host = entry.get("host", {})
    print(f"  committed on {host.get('cpu_count')}-core "
          f"{host.get('machine')} / jax {host.get('jax')}; warn-only "
          f"(shared 2-core CI hosts make hard throughput gates meaningless)")
    print("netsim_perf --check:", "WARNINGS above" if warned else "ok")
    return 0


def main(argv) -> int:
    if "--check" in argv:
        return check()
    enable_compile_cache()
    res = bench()
    write_bench(res)
    print(json.dumps(res, indent=1))
    print(f"wrote {BENCH_FILE}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
