"""The simulator's own profiler spans and stage scopes.

* Host spans (``jax.profiler.TraceAnnotation``): ``simulate_grid`` opens
  ``netsim.grid`` (args ``lanes``, ``ticks``) around
  ``netsim.grid.statics`` / ``.launch`` / ``.concat``;
  ``SimController.step`` opens ``netsim.step`` (args ``step``,
  ``ticks``) around ``netsim.step.action``, ``run_window``'s
  ``netsim.window.batch`` / ``.launch`` / ``.unbatch`` and
  ``netsim.step.observe``.  A trace read back with ``ProfileData``
  holds each with its count, nesting and args.
* Device scopes (``jax.named_scope``): the lowered grid program carries
  the ten stage scopes on the staged XLA tick, and on the kernel path
  the seven that run outside the kernel plus ``netsim.kernel_operands``
  around the kernel's operands.
* Neither changes a result bit nor the one compile per grid.
"""
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.netsim import (SimController, SimParams, WorkloadBuilder,
                               core_trace_count, grid_from_params,
                               make_leaf_spine, simulate_grid)
from repro.core.netsim.simulator import (_grid_core, _resolve_routing,
                                         _stacked_statics, wl_arrays)

STAGES = ("starts", "instance_view", "share", "queues", "marking",
          "progress", "symphony", "rate_control", "segments", "metrics")


@pytest.fixture(scope="module")
def small():
    topo = make_leaf_spine(8, 2, 2)
    b = WorkloadBuilder()
    b.add_ring_job(hosts=list(range(8)), ring_size=4, chunk_bytes=1e6,
                   passes=1)
    return topo, b.build()


def _grid(n_ticks, **path):
    cfg = SimParams(n_ticks=n_ticks, window=8, record_every=10, **path)
    return grid_from_params([cfg._replace(sym_on=True),
                             cfg._replace(sym=cfg.sym._replace(k=0.1))])


def _host_spans(trace_dir):
    """``name -> [(start_ns, end_ns, line, args)]`` of the ``netsim.*``
    events in the newest trace under ``trace_dir``."""
    path = max(trace_dir.rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("netsim."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         (plane.name, line.name), dict(ev.stats)))
    return out


def _inside(child, parents):
    """Each ``child`` span lies within one of ``parents`` on its line."""
    return all(any(p[2] == c[2] and p[0] <= c[0] and c[1] <= p[1]
                   for p in parents) for c in child)


def test_host_spans_of_a_grid_and_three_steps(small, tmp_path):
    topo, wl = small
    struct, knobs = _grid(40)
    cfg = SimParams(n_ticks=40, window=8, record_every=10)
    warm = SimController(topo, wl, cfg, window_ticks=20, seed=1)
    warm.step({"tau": 0.3})
    jax.block_until_ready(simulate_grid(topo, wl, struct, knobs, (0, 1)))
    ctl = SimController(topo, wl, cfg, window_ticks=20, seed=1)

    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(simulate_grid(topo, wl, struct, knobs, (0, 1)))
        jax.block_until_ready(simulate_grid(topo, wl, struct, knobs, (0, 1),
                                            chunk_knobs=1))
        ctl.step()
        ctl.step({"tau": 0.3})
        ctl.step({"k": 0.05})
    sp = _host_spans(tmp_path)

    counts = {n: len(v) for n, v in sp.items()}
    assert counts == {
        "netsim.grid": 2, "netsim.grid.statics": 2, "netsim.grid.launch": 2,
        "netsim.grid.concat": 1,
        "netsim.step": 3, "netsim.step.action": 2, "netsim.step.observe": 3,
        "netsim.window.batch": 3, "netsim.window.launch": 3,
        "netsim.window.unbatch": 3}
    for name in ("statics", "launch", "concat"):
        assert _inside(sp[f"netsim.grid.{name}"], sp["netsim.grid"]), name
    for name in ("step.action", "step.observe", "window.batch",
                 "window.launch", "window.unbatch"):
        assert _inside(sp[f"netsim.{name}"], sp["netsim.step"]), name
    assert not _inside(sp["netsim.window.launch"], sp["netsim.grid"])

    for _, _, _, args in sp["netsim.grid"]:
        assert args == {"lanes": 4, "ticks": 40}
    steps = sorted(sp["netsim.step"])
    assert [a["step"] for *_, a in steps] == [0, 1, 2]
    assert all(a["ticks"] == 20 for *_, a in steps)
    # the action spans lie in the second and third steps only
    firsts = [s for s, *_ in steps]
    assert all(a[0] > firsts[0] for a in sp["netsim.step.action"])


def _scopes(text):
    return set(re.findall(r"netsim\.[a-z_]+", text))


_IN_KERNEL = {"share", "queues", "symphony"}


@pytest.mark.parametrize("path, expect", [
    ({"backend": "xla"}, set(STAGES)),
    ({"backend": "pallas"}, set(STAGES) - _IN_KERNEL | {"kernel_operands"}),
    ({"backend": "pallas", "segsum": "onehot", "blk": 16},
     set(STAGES) - _IN_KERNEL | {"kernel_operands"}),
], ids=["xla", "kernel", "kernel_tiled"])
def test_lowered_grid_program_carries_stage_scopes(small, path, expect):
    """Every stage's ops carry ``netsim.<stage>`` in their location.  On
    the kernel path sharing, queues and the Symphony scatter run inside
    the kernel, which carries no stage scope."""
    topo, wl = small
    struct, knobs = _grid(20, **path)
    struct, mode = _resolve_routing(struct, "ecmp")
    stacked, keys = _stacked_statics(topo, wl, mode, (0,), struct)
    text = _grid_core.lower(stacked, wl_arrays(wl, struct.dt), struct,
                            knobs, keys).as_text(debug_info=True)
    assert _scopes(text) == {f"netsim.{s}" for s in expect}
    if path["backend"] == "pallas":
        assert "netsim_tick" in text


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_tracing_changes_no_bit_and_no_compile(small, tmp_path, backend):
    topo, wl = small
    struct, knobs = _grid(30, backend=backend)
    c0 = core_trace_count()
    plain = simulate_grid(topo, wl, struct, knobs, (0, 1))
    assert core_trace_count() - c0 == 1
    with jax.profiler.trace(str(tmp_path)):
        traced = simulate_grid(topo, wl, struct, knobs, (0, 1))
        jax.block_until_ready(traced)
    assert core_trace_count() - c0 == 1
    for a, b in zip(jax.device_get(plain), jax.device_get(traced)):
        assert np.array_equal(a, b)

    cfg = SimParams(n_ticks=30, window=8, record_every=10, backend=backend)
    runs = []
    for traced_run in (False, True):
        ctl = SimController(topo, wl, cfg, window_ticks=10, seed=2)
        if traced_run:
            with jax.profiler.trace(str(tmp_path / "steps")):
                obs = [ctl.step({"tau": t})[1] for t in (0.2, 0.3, 0.4)]
        else:
            obs = [ctl.step({"tau": t})[1] for t in (0.2, 0.3, 0.4)]
        runs.append((jax.device_get(ctl.state),
                     [jax.device_get(o.samples) for o in obs]))
    assert jax.tree.all(jax.tree.map(np.array_equal, runs[0], runs[1]))
