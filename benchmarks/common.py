"""Shared scenario registry + result caching for the paper benchmarks.

All network scenarios follow paper Table 1 defaults: 4 ToR x 4 spine,
10 Gbps, 32 nodes arranged as 4 parallel rings of 8 (the 8x4 logical 2-D),
chunk 8 MB, RED(50/100KB, 0.2), DCQCN-style CC, tau=0.25, T_win=100us,
k=0.01.  Larger scales (128 nodes = 32x4) follow the same pattern.

The declarative **scenario registry** is the single source of truth for
benchmark and test setups: each entry builds a ``Built(topo, wl, cfg,
routing)`` tuple from keyword overrides.  Fig-scripts and the system tests
both consume it::

    from benchmarks.common import build_scenario
    topo, wl, cfg, routing = build_scenario("table1_ring", passes=4)

Register new scenarios with the :func:`scenario` decorator.  Scenarios may
also declare **sweep axes** (named RuntimeKnobs dimensions such as ``tau``,
``k``, ``t_win_ticks``); ``run_scenario_grid`` crosses them and dispatches
the whole grid through ``simulate_grid`` — one compile for the entire
sweep, vmapped over knob points x seeds.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, NamedTuple, Sequence

import jax
import numpy as np

from repro.core.netsim import (SimParams, Topology, Workload, WorkloadBuilder,
                               grid_from_params, make_fat_tree,
                               make_leaf_spine, metrics, resolve_grid_mesh,
                               scale_for_hosts, simulate, simulate_grid,
                               simulate_seeds)
from repro.core.netsim.topology import DEFAULT_LINK_BPS as LINK_BPS

CACHE = Path(__file__).resolve().parent / ".cache.json"
# JAX's persistent compilation cache, when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed path, because the path is part of every cache key.
COMPILE_CACHE = Path(__file__).resolve().parents[1] / ".jax_cache"
QUICK = os.environ.get("BENCH_QUICK", "0") != "0"

# Bumped whenever the cache key scheme or result layout changes; older
# cache files are discarded wholesale instead of serving stale entries.
CACHE_SCHEMA = 3


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX reads it itself);
    otherwise the cache lives at the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    return str(COMPILE_CACHE)


def grid_devices():
    """Default multi-device dispatch for the benchmark layer, from the
    ``BENCH_DEVICES`` env var: ``"auto"`` = all local devices, an integer
    = that many, unset/empty/"1" = single-device dispatch (None)."""
    val = os.environ.get("BENCH_DEVICES", "").strip()
    if not val or val == "1":
        return None
    return "auto" if val == "auto" else int(val)


def device_fingerprint() -> str:
    """Backend + device/mesh configuration a result was produced under.

    Folded into every ``cached()`` key: single- and multi-device runs of
    the same scenario measure different dispatch paths (and wall clocks),
    so they must not collide in the result cache."""
    dev = grid_devices()
    mesh = resolve_grid_mesh(devices=dev)
    used = 1 if mesh is None else int(mesh.devices.size)
    return f"{jax.default_backend()}:{jax.device_count()}:grid{used}"


def _config_hash(config) -> str:
    blob = json.dumps(config, sort_keys=True, default=repr)
    return hashlib.sha1(blob.encode()).hexdigest()[:10]


def cached(name: str, fn, config=None):
    """Memoize a benchmark result in ``.cache.json``.

    The key folds in a hash of ``config`` — the overrides/sweep values the
    run depends on — plus the device/mesh fingerprint, so re-running a
    scenario with different parameters or on a different device
    configuration misses the cache instead of silently returning stale
    JSON.
    """
    cache = {}
    if CACHE.exists():
        data = json.loads(CACHE.read_text())
        if data.get("__schema__") == CACHE_SCHEMA:
            cache = data
    key = f"{name}{'@' + _config_hash(config) if config is not None else ''}" \
          f"::{device_fingerprint()}" \
          f"{'::quick' if QUICK else ''}"
    if key in cache:
        return cache[key]
    t0 = time.time()
    out = fn()
    out["_wall_s"] = round(time.time() - t0, 1)
    cache[key] = out
    cache["__schema__"] = CACHE_SCHEMA
    CACHE.write_text(json.dumps(cache, indent=1))
    return out


# --------------------------------------------------------------- registry
class Built(NamedTuple):
    """A fully-materialized scenario ready for ``simulate``."""
    topo: Topology
    wl: Workload
    cfg: SimParams
    routing: str = "ecmp"


# Named knob axes: how a sweep value lands in SimParams.  Every applier
# touches only RuntimeKnobs fields, so any cross-product of these axes
# stays a single compiled program under ``simulate_grid``.
KNOB_APPLIERS: dict[str, Callable[[SimParams, object], SimParams]] = {
    "sym": lambda c, v: c._replace(sym_on=bool(v)),
    "pq": lambda c, v: c._replace(pq_on=bool(v)),
    "tau": lambda c, v: c._replace(sym=c.sym._replace(tau=v)),
    "k": lambda c, v: c._replace(sym=c.sym._replace(k=v)),
    "alpha_max": lambda c, v: c._replace(sym=c.sym._replace(alpha_max=v)),
    "t_win_ticks": lambda c, v: c._replace(sym_win_ticks=int(v)),
    "sym_start_tick": lambda c, v: c._replace(sym_start_tick=int(v)),
    "red_pmax": lambda c, v: c._replace(red_pmax=v),
    "red_kmin": lambda c, v: c._replace(red_kmin=v),
    "red_kmax": lambda c, v: c._replace(red_kmax=v),
    "cc_rai": lambda c, v: c._replace(cc_rai=v),
    "cc_g": lambda c, v: c._replace(cc_g=v),
}


@dataclass(frozen=True)
class SweepAxis:
    """A declarative sweep dimension: a knob-axis name + default values."""
    knob: str                 # key into KNOB_APPLIERS
    values: tuple             # default grid values (full mode)
    quick: tuple | None = None  # reduced values under BENCH_QUICK

    def points(self) -> tuple:
        return self.quick if (QUICK and self.quick is not None) else self.values


@dataclass(frozen=True)
class Scenario:
    name: str
    description: str
    build: Callable[..., Built]
    sweeps: tuple[SweepAxis, ...] = ()


SCENARIOS: dict[str, Scenario] = {}


def scenario(name: str, description: str = "",
             sweeps: Sequence[SweepAxis] = ()):
    """Register a scenario builder under ``name``, optionally with the
    declarative knob-sweep axes the paper evaluates it over."""
    def deco(fn):
        SCENARIOS[name] = Scenario(name, description, fn, tuple(sweeps))
        return fn
    return deco


def knob_combos(axes: dict[str, Sequence]) -> list[tuple]:
    """Row-major cross product of the axis values: the single source of
    truth for how grid point i maps back to axis values (``knob_grid``
    and any consumer labelling grid results must share this order)."""
    return list(itertools.product(*axes.values()))


def knob_grid(cfg: SimParams, axes: dict[str, Sequence]) -> list[SimParams]:
    """Cross-product of knob axes applied to a base config; point i
    corresponds to ``knob_combos(axes)[i]``."""
    for name in axes:
        if name not in KNOB_APPLIERS:
            raise KeyError(
                f"unknown knob axis {name!r}; have {sorted(KNOB_APPLIERS)}")
    cfgs = []
    for combo in knob_combos(axes):
        c = cfg
        for name, v in zip(axes, combo):
            c = KNOB_APPLIERS[name](c, v)
        cfgs.append(c)
    return cfgs


def sweep_axes_for(name: str) -> dict[str, tuple]:
    """The registered default sweep axes of a scenario (may be empty)."""
    return {ax.knob: ax.points() for ax in SCENARIOS[name].sweeps}


def run_grid(topo, wl, cfgs: Sequence[SimParams], seeds, routing="ecmp",
             chunk_knobs: int | None = None, devices="env", mesh=None, **bg):
    """Run a knob grid through the one-compile batched executor.

    ``devices``/``mesh`` shard the grid's lane axis across a 1-D device
    mesh (see ``simulate_grid``); the default ``"env"`` defers to the
    ``BENCH_DEVICES`` env var (unset = single-device dispatch).

    Returns a SimResult with leading ``[K, S]`` axes, K = len(cfgs).
    """
    if devices == "env":
        devices = grid_devices()
    struct, knobs = grid_from_params(list(cfgs))
    res = simulate_grid(topo, wl, struct, knobs, seeds, routing=routing,
                        chunk_knobs=chunk_knobs, devices=devices, mesh=mesh,
                        **bg)
    return jax.block_until_ready(res)


def run_scenario_grid(name: str, axes: dict[str, Sequence] | None = None,
                      seeds=(0,), chunk_knobs: int | None = None,
                      devices="env", mesh=None, **overrides):
    """Build a registered scenario and sweep its knob axes in one compile.

    ``axes`` defaults to the scenario's registered sweep axes; ``devices``
    / ``mesh`` shard the grid lanes across devices.  Returns ``(built,
    cfgs, result)`` where ``cfgs[i]`` describes grid point i and
    ``result`` carries ``[K, S]`` leading axes.
    """
    built = build_scenario(name, **overrides)
    axes = sweep_axes_for(name) if axes is None else axes
    cfgs = knob_grid(built.cfg, axes)
    res = run_grid(built.topo, built.wl, cfgs, seeds, routing=built.routing,
                   chunk_knobs=chunk_knobs, devices=devices, mesh=mesh)
    return built, cfgs, res


def build_scenario(name: str, **overrides) -> Built:
    """Materialize a registered scenario with keyword overrides."""
    try:
        sc = SCENARIOS[name]
    except KeyError:
        raise KeyError(f"unknown scenario {name!r}; have {list_scenarios()}")
    return sc.build(**overrides)


def list_scenarios() -> list[str]:
    return sorted(SCENARIOS)


def _horizon_cfg(wl, mult: float = 4.0, dt: float = 10e-6,
                 **kw) -> SimParams:
    """SimParams sized to a multiple of the job-0 lockstep lower bound."""
    ideal = metrics.ideal_cct(wl, 0, LINK_BPS)
    return SimParams(n_ticks=int(ideal * mult / dt), dt=dt, window=64, **kw)


# ------------------------------------------------- Table-1 building blocks
def table1_topo(n_hosts: int = 32):
    if n_hosts == 32:
        return make_leaf_spine(32, 4, 4)
    return scale_for_hosts(n_hosts)


def table1_workload(n_hosts: int = 32, ring: int = 8, chunk: float = 8e6,
                    passes: int = 8, barrier: bool = False,
                    compute_gap: float = 0.0,
                    chunk_schedule=None):
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk_schedule if chunk_schedule is not None
                   else chunk,
                   passes=passes, barrier=barrier, compute_gap=compute_gap)
    return b.build()


@scenario("table1_ring",
          "Paper Table-1: 2-tier leaf-spine, parallel 1-D ring allreduce",
          sweeps=(
              SweepAxis("sym", (False, True)),
              SweepAxis("tau", (0.1, 0.25, 0.5), quick=(0.25,)),
              SweepAxis("k", (1e-3, 1e-2, 1e-1), quick=(1e-2,)),
          ))
def _table1_ring(n_hosts: int = 32, ring: int = 8, chunk: float = 8e6,
                 passes: int = 6, barrier: bool = False,
                 compute_gap: float = 0.0, chunk_schedule=None,
                 horizon_mult: float = 4.0, sym: bool = False,
                 share_policy: str = "proportional") -> Built:
    topo = table1_topo(n_hosts)
    wl = table1_workload(n_hosts, ring, chunk, passes, barrier, compute_gap,
                         chunk_schedule)
    return Built(topo, wl, _horizon_cfg(wl, horizon_mult, sym_on=sym,
                                        share_policy=share_policy))


@scenario("table1_2d",
          "Paper §4.6: 2-D ring collective on the Table-1 fabric",
          sweeps=(
              SweepAxis("k", (1e-4, 1e-3, 1e-2, 1e-1),
                        quick=(1e-3, 1e-2, 1e-1)),
          ))
def _table1_2d(n_hosts: int = 32, d0: int = 8, chunk: float = 8e6,
               passes: int = 3, horizon_mult: float = 5.0,
               sym: bool = False) -> Built:
    topo = table1_topo(n_hosts)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=d0, passes=passes,
                   chunk_bytes=chunk, dims=(d0, n_hosts // d0))
    wl = b.build()
    return Built(topo, wl, _horizon_cfg(wl, horizon_mult, sym_on=sym))


@scenario("two_flow_fig9",
          "Paper Fig. 9 hardware prototype: two flows, one ToR egress port")
def _two_flow_fig9(delay_a: float = 0.25, size: float = 1e9,
                   sym: bool = False) -> Built:
    # hosts 0,1 send to host 2: both flows share the ToR egress port
    # (acc_down of host 2), exactly the prototype's single-port contention.
    # Same job, flow B tagged one step ahead (step in the UDP sport, §4.7):
    # B is the outpacing flow, A the lagging one.
    topo = make_leaf_spine(4, 2, 2)
    b = WorkloadBuilder()
    b.add_chain_job(pairs=[(0, 2), (1, 2)], steps=1, chunk_bytes=size,
                    step_offsets=[0, 1], flow_starts=[delay_a, 0.0])
    wl = b.build()
    t_end = 3.2 * (size / 1.25e9) + delay_a + 0.2
    cfg = SimParams(n_ticks=int(t_end / 20e-6), dt=20e-6, window=8,
                    sym_on=sym)
    return Built(topo, wl, cfg, routing="balanced")


@scenario("multi_tenant_pair",
          "Paper Fig. 7a/b: two co-located jobs, job B delayed")
def _multi_tenant_pair(n_hosts: int = 64, ring: int = 8, chunk: float = 8e6,
                       passes: int = 3, delay: float = 0.1,
                       sym: bool = False) -> Built:
    topo = table1_topo(n_hosts)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk, passes=passes, barrier=False)
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk, passes=passes, barrier=False,
                   start_time=delay)
    wl = b.build()
    horizon = int((0.15 * passes + 0.8) / 10e-6)
    return Built(topo, wl, SimParams(n_ticks=horizon, window=64, sym_on=sym))


@scenario("fat_tree_ring",
          "3-tier multi-pod fat-tree, inter-pod interleaved ring allreduce")
def _fat_tree_ring(n_pods: int = 2, tors_per_pod: int = 2,
                   spines_per_pod: int = 2, hosts_per_tor: int = 4,
                   n_cores: int | None = None,
                   core_oversubscription: float = 1.0,
                   ring: int | None = None, chunk: float = 4e6,
                   passes: int = 2, barrier: bool = False,
                   horizon_mult: float = 6.0, sym: bool = False) -> Built:
    topo = make_fat_tree(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor,
                         n_cores, core_oversubscription=core_oversubscription)
    n = topo.n_hosts
    ring = n // 2 if ring is None else ring
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n)), ring_size=ring, chunk_bytes=chunk,
                   passes=passes, barrier=barrier)
    wl = b.build()
    return Built(topo, wl, _horizon_cfg(wl, horizon_mult, sym_on=sym))


@scenario("fat_tree_halving_doubling",
          "3-tier fat-tree, recursive halving-doubling allreduce")
def _fat_tree_hd(n_pods: int = 2, tors_per_pod: int = 2,
                 spines_per_pod: int = 2, hosts_per_tor: int = 4,
                 core_oversubscription: float = 1.0, chunk: float = 4e6,
                 passes: int = 1, horizon_mult: float = 6.0,
                 sym: bool = False) -> Built:
    topo = make_fat_tree(n_pods, tors_per_pod, spines_per_pod, hosts_per_tor,
                         core_oversubscription=core_oversubscription)
    b = WorkloadBuilder()
    b.add_halving_doubling_job(hosts=list(range(topo.n_hosts)),
                               chunk_bytes=chunk, passes=passes)
    wl = b.build()
    return Built(topo, wl, _horizon_cfg(wl, horizon_mult, sym_on=sym))


def multipod_topo(n_hosts: int, hosts_per_tor: int = 8, tors_per_pod: int = 4,
                  spines_per_pod: int = 4, n_cores: int = 8,
                  core_oversubscription: float = 2.0) -> Topology:
    """3-tier multi-pod FatTree scaled to ``n_hosts`` (32 hosts/pod by
    default: 128 -> 4 pods, 256 -> 8, 512 -> 16), with a 1:2 core tier
    matching the paper's oversubscribed multi-pod interconnects (§4.1)."""
    per_pod = hosts_per_tor * tors_per_pod
    if n_hosts % per_pod:
        raise ValueError(f"hosts ({n_hosts}) must divide evenly over "
                         f"{per_pod}-host pods")
    return make_fat_tree(n_hosts // per_pod, tors_per_pod, spines_per_pod,
                         hosts_per_tor, n_cores,
                         core_oversubscription=core_oversubscription)


@scenario("fat_tree_multipod",
          "128-512 host 3-tier multi-pod FatTree, inter-pod interleaved "
          "rings — the Table-2/Fig-8-at-scale sweep fabric",
          sweeps=(
              SweepAxis("sym", (False, True)),
              SweepAxis("tau", (0.1, 0.25, 0.5), quick=(0.25,)),
              SweepAxis("k", (1e-3, 1e-2, 1e-1), quick=(1e-2,)),
              SweepAxis("t_win_ticks", (5, 10, 20), quick=(5,)),
          ))
def _fat_tree_multipod(n_hosts: int = 128, ring: int = 32,
                       chunk: float = 2e6, passes: int = 1,
                       barrier: bool = False, horizon_mult: float = 4.0,
                       sym: bool = False, deploy: str = "tor",
                       core_oversubscription: float = 2.0,
                       coarse: bool = True) -> Built:
    """The 512-host-class sweep scenario: parallel ``ring``-size rings
    striped across pods, coarse 20us ticks by default (control-loop
    windows rescaled to keep T_win = 100us / 40us CC epochs) so dense
    knob grids stay affordable at 512 hosts."""
    topo = multipod_topo(n_hosts,
                         core_oversubscription=core_oversubscription)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(n_hosts)), ring_size=ring,
                   chunk_bytes=chunk, passes=passes, barrier=barrier)
    wl = b.build()
    extra = dict(sym_win_ticks=5, cc_epoch_ticks=2) if coarse else {}
    cfg = _horizon_cfg(wl, horizon_mult, dt=20e-6 if coarse else 10e-6,
                       sym_on=sym, deploy=deploy, **extra)
    return Built(topo, wl, cfg)


@scenario("tenant_churn",
          "Continuous multi-tenant replay over the multipod fabric: "
          "Poisson tenant arrivals/departures plus a dependency-triggered "
          "follow-on job (the online control plane's serving workload)",
          sweeps=(
              SweepAxis("sym", (False, True)),
              SweepAxis("tau", (0.1, 0.25, 0.5), quick=(0.25,)),
          ))
def _tenant_churn(n_hosts: int = 64, ring: int = 8, chunk: float = 2e6,
                  passes: int = 2, rate_hz: float = 150.0,
                  churn_horizon_s: float = 0.04, max_tenants: int = 4,
                  trigger_delay: float = 2e-3, churn_seed: int = 0,
                  horizon_mult: float = 6.0, sym: bool = False,
                  deploy: str = "tor",
                  core_oversubscription: float = 2.0) -> Built:
    """Job 0 is a long-lived tenant; job 1 is dependency-triggered (starts
    when job 0 completes its first collective, cf. CCL_Simulator's policy
    rules); the remaining ring-sized host groups serve a Poisson stream of
    short-lived tenants.  All arrivals are lowered to traced arrays, so
    churn grids still run under the one-compile grid/shard executors."""
    topo = multipod_topo(n_hosts,
                         core_oversubscription=core_oversubscription)
    groups = [list(range(g * ring, (g + 1) * ring))
              for g in range(n_hosts // ring)]
    if len(groups) < 3:
        raise ValueError("tenant_churn needs >= 3 ring-sized host groups")
    b = WorkloadBuilder()
    base = b.add_ring_job(hosts=groups[0], ring_size=ring, chunk_bytes=chunk,
                          passes=passes, barrier=False)
    follow = b.add_ring_job(hosts=groups[1], ring_size=ring,
                            chunk_bytes=chunk, passes=passes, barrier=False)
    b.set_trigger(follow, after_job=base, collectives=1,
                  delay=trigger_delay)
    b.add_poisson_churn(groups[2:], rate_hz=rate_hz,
                        horizon_s=churn_horizon_s, ring_size=ring,
                        chunk_bytes=chunk / 4, passes=1, seed=churn_seed,
                        max_jobs=max(1, max_tenants // 2 if QUICK
                                     else max_tenants))
    wl = b.build()
    cfg = _horizon_cfg(wl, horizon_mult, dt=20e-6, sym_on=sym,
                       deploy=deploy, sym_win_ticks=5, cc_epoch_ticks=2)
    return Built(topo, wl, cfg)


@scenario("hierarchical_tor",
          "Hierarchical allreduce: intra-ToR rings + inter-ToR leader ring")
def _hierarchical_tor(n_hosts: int = 32, n_tors: int = 4, n_spines: int = 4,
                      chunk: float = 8e6, passes: int = 2,
                      horizon_mult: float = 6.0, sym: bool = False) -> Built:
    topo = make_leaf_spine(n_hosts, n_tors, n_spines)
    b = WorkloadBuilder()
    b.add_hierarchical_job(hosts=list(range(n_hosts)),
                           group_size=topo.hosts_per_tor,
                           chunk_bytes=chunk, passes=passes)
    wl = b.build()
    return Built(topo, wl, _horizon_cfg(wl, horizon_mult, sym_on=sym))


# ------------------------------------------------------------ run helpers
def default_params(n_ticks: int, sym: bool = False, **kw) -> SimParams:
    return SimParams(n_ticks=n_ticks, window=64, sym_on=sym, **kw)


def kernel_tuning() -> dict:
    """Fused-kernel tuning knobs for the benchmark layer, overridable via
    env (``BENCH_SEGSUM``, ``BENCH_BLK``, ``BENCH_TICK_WINDOW``) so perf
    sweeps over the kernel configuration need no code edits.  Returns
    ``SimParams`` override kwargs; the defaults are the committed
    BENCH_netsim.json trajectory configuration (scatter segsum, untiled,
    tick_window=5 — windows amortize state HBM round-trips, see
    ``roofline.netsim_tick_tiled``)."""
    segsum = os.environ.get("BENCH_SEGSUM", "scatter")
    blk = os.environ.get("BENCH_BLK", "")
    tw = os.environ.get("BENCH_TICK_WINDOW", "5")
    return {"segsum": segsum,
            "blk": int(blk) if blk else None,
            "tick_window": int(tw) if tw else 1}


def params_for_seconds(horizon_s: float, sym: bool = False,
                       coarse: bool = False, **kw) -> SimParams:
    """coarse=True runs at 20 us ticks (halves cost for multi-second JCT
    scenarios; control-loop windows rescaled to keep T_win=100us, 40us CC
    epochs)."""
    dt = 20e-6 if coarse else 10e-6
    extra = dict(sym_win_ticks=5, cc_epoch_ticks=2) if coarse else {}
    extra.update(kw)
    return SimParams(n_ticks=int(horizon_s / dt) // 20 * 20, dt=dt,
                     window=64, sym_on=sym, **extra)


def run_one(topo, wl, cfg, routing="ecmp", seed=0, **bg):
    res = simulate(topo, wl, cfg, routing=routing, seed=seed, **bg)
    return jax.block_until_ready(res)


def run_scenario(name: str, seed: int = 0, **overrides):
    """Build and run a registered scenario; returns (built, result)."""
    built = build_scenario(name, **overrides)
    return built, run_one(built.topo, built.wl, built.cfg,
                          routing=built.routing, seed=seed)


def summarize(res, wl, cfg, job=0):
    cct = metrics.cct_seconds(res, wl, cfg)
    return {
        "cct_s": float(cct[job]) if np.isfinite(cct[job]) else None,
        "max_overlap": int(metrics.max_overlap(res, cfg, job)),
        "ideal_s": metrics.ideal_cct(wl, job, LINK_BPS),
    }


def seeds_for(n_full: int, n_quick: int = 3):
    return list(range(n_quick if QUICK else n_full))


def run_seeds(topo, wl, cfg, routing, seeds, devices="env", mesh=None, **bg):
    """Batched multi-seed run (vmap), seed lanes sharded like grid lanes."""
    if devices == "env":
        devices = grid_devices()
    res = simulate_seeds(topo, wl, cfg, routing, seeds, devices=devices,
                        mesh=mesh, **bg)
    return jax.block_until_ready(res)
