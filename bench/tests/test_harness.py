"""The harness itself, on the CPU in seconds: cells, metrics and mixes
are found by name from data files alone; the roofline's work count
depends on shapes alone; the trace reduction reads a small recorded
trace; and a whole run at a tiny size prints a last line of the
expected shape."""
from __future__ import annotations

import dataclasses
import functools
import io
import json
import shutil

import jax
import numpy as np
import pytest

from lib import cells, harness, trace as tr
from lib.chip import CompileClock
from lib.roofline import peak, tick_bytes
import tiny
from tiny import BENCH, cell, now

FIXTURE = BENCH / "tests" / "fixtures" / "online_trace.json"


def test_cell_and_metric_found_by_name(tmp_path):
    """A new cell, mix, limit and metric are data files and entries."""
    base = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (base / d).mkdir(parents=True)
    shutil.copy(BENCH / "configs" / "table1_leafspine32.json",
                base / "configs" / "dummy_cfg.json")
    (base / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"kind": "sweep", "axes": {"k": [0.01]}, "path": {"backend": "xla"},
         "trace_units": 1}))
    (base / "limits" / "dummy.cell.json").write_text('{"int_mismatch": 0}')
    (base / "metrics" / "dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = {
        "workloads": [{"name": "dummy.cell", "config": "dummy_cfg",
                       "traffic": "dummy_mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "lane_ticks_per_s", "unit": "lane-ticks/s",
                        "workloads": ["dummy.cell"]},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "dummy.metric", "unit": "us",
                       "moves": "lane_ticks_per_s"},
                      {"name": "other", "unit": "%", "moves": "step_ms"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = cells.load_cell("dummy.cell", tmp_path / "BENCHMARK.json", base)
    assert c.traffic["axes"] == {"k": [0.01]}
    assert c.config["fabric"]["n_hosts"] == 32
    assert [m["name"] for m in c.end_to_end] == ["lane_ticks_per_s",
                                                 "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["dummy.metric"]
    assert cells.metric_reader("dummy.metric", base)(None) == 42.0


def _same_deployment(cfg, base=BENCH):
    """The kind files' reference constructors and the simulator's agree on
    every array the tick reads."""
    from lib.deploy import program_inputs
    from reference.build import deployment
    from repro.core.netsim.simulator import build_static, wl_arrays

    topo, wl = program_inputs(cfg, base)
    st = build_static(topo, wl, "ecmp", 5, dt=cfg["engine"]["dt"])
    wla = wl_arrays(wl, cfg["engine"]["dt"])
    dep = deployment(cfg, base)
    same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))
    assert same(st.path_table, dep.paths) and same(st.n_paths, dep.n_paths)
    assert same(st.cap, dep.cap.astype(np.float32))
    assert same(st.link_dom, dep.dom) and dep.D + 1 == st.dom_pad.shape[0]
    for f in ("pred", "job", "phase", "pass_steps", "total_steps",
              "n_phases", "n_segs", "step_offset", "trig_job", "trig_seg"):
        assert same(getattr(wla, f), getattr(dep, f)), f
    for f, g in (("sps", "sps"), ("fstart_ticks", "fstart"),
                 ("gap_ticks", "gap"), ("start_ticks", "seg_ready0"),
                 ("trig_delay_ticks", "trig_delay")):
        assert same(getattr(wla, f), getattr(dep, g)), f
    assert same(wla.chunk_sched, dep.chunk.astype(np.float32))
    assert same(st.cap[st.routes[:, 0]], dep.line_rate.astype(np.float32))


@pytest.mark.parametrize("path", sorted(BENCH.glob("configs/*.json")),
                         ids=lambda p: p.stem)
def test_reference_and_program_build_the_same_deployment(path):
    """At the configuration's full size."""
    _same_deployment(cells.load_json(path))


def test_every_cell_of_the_benchmark_loads():
    spec = cells.load_json(cells.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        c = cells.load_cell(w["name"])
        assert c.limits and c.per_layer
        for m in c.per_layer:
            assert callable(cells.metric_reader(m["name"]))


def test_roofline_count_is_the_same_for_xla_and_the_kernel():
    """The work of a tick comes from the configuration's shapes, never
    from the engine path the mix names."""
    kern = cells.load_cell("multipod512.sweep8.kernel")
    xla = cells.load_cell("multipod512.sweep8.xla.4chip")
    assert kern.traffic["path"]["backend"] != xla.traffic["path"]["backend"]
    ctx = [harness.MetricContext(c, {"units": 1, "lane_ticks": 1},
                                 None, "TPU v5 lite") for c in (kern, xla)]
    assert ctx[0].tick_bytes() == ctx[1].tick_bytes() > 0
    # 512 flows x 64 instances x 7 words dominate: 2 x 4 x 229,376 words
    assert ctx[0].tick_bytes() == 2 * 4 * (3 * 512 + 7 * 512 * 64 + 1793
                                           + 5 * 65 + 3 + 2)
    assert tick_bytes(F=32, W=64, L=96, J=1, D=4) == \
        2 * 4 * (96 + 7 * 2048 + 97 + 25 + 3 + 2)
    with pytest.raises(KeyError):
        peak("TPU v99")


def _covered(intervals, a, b):
    return any(s <= a and b <= e for s, e in intervals)


def _brute_force(doc):
    """Busy time and host-only time of the fixture by elementary segments
    between all event edges, with leaves found pairwise."""
    ev = {p: ls for p, ls in doc["planes"]}
    host = [e for _, es in ev["/host:CPU"] for e in es]
    w0, w1 = [(s, e) for n, s, e in host if n == "bench.window"][-1]
    ops = [(max(s, w0), min(e, w1)) for _, es in ev["/device:TPU:0"]
           for _, s, e in es if e > w0 and s < w1]
    leaves = [a for a in ops if not any(
        b != a and a[0] <= b[0] and b[1] <= a[1] for b in ops)]
    steps = [(s, e) for n, s, e in host if n == "bench.step"]
    edges = sorted({w0, w1} | {x for iv in ops + steps for x in iv})
    busy = host_only = 0.0
    for a, b in zip(edges, edges[1:]):
        if _covered(leaves, a, b):
            busy += b - a
        elif _covered(steps, a, b):
            host_only += b - a
    return (w1 - w0) / 1e9, busy / 1e9, host_only / 1e9


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_trace_reduction_on_a_recorded_trace():
    doc = json.loads(FIXTURE.read_text())
    red = tr.reduce(doc["planes"], doc["texts"], 1)
    window, busy, host_only = _brute_force(doc)
    assert red.window_s == pytest.approx(window)
    assert red.busy_s == pytest.approx(busy) == doc["expect"]["busy_s"]
    assert red.host_s["bench.step"] == pytest.approx(host_only)
    assert red.host_s["bench.step"] == pytest.approx(
        doc["expect"]["host_s_step"])
    assert red.span_count["bench.step"] == doc["expect"]["steps"]
    kernel = 'custom_call_target="tpu_custom_call"'
    assert red.matching(kernel) == pytest.approx(doc["expect"]["kernel_s"])
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.busy_s)
    bd = tr.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_leaves_drop_enclosing_loops():
    evs = [(0, 100, "while"), (0, 10, "a"), (10, 30, "b"), (50, 60, "c")]
    assert [e[2] for e in tr.leaves(evs)] == ["a", "b", "c"]
    assert tr.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]


def _one_cell_per_kind() -> list[str]:
    """The first cell of each (configuration, traffic kind) pair."""
    first = {}
    for w in cells.load_json(cells.ROOT / "BENCHMARK.json")["workloads"]:
        tr = cells.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        first.setdefault((w["config"], tr["kind"]), w["name"])
    return list(first.values())


@pytest.mark.parametrize("name", _one_cell_per_kind())
def test_tiny_run_prints_a_result_line(name):
    out, err = io.StringIO(), io.StringIO()
    c = cell(name)
    harness.run(c, 2**31 + 3, 0.5, False, jax.devices()[:1], now(),
                CompileClock(), out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(last)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["attempted"] >= 1
    names = {m["name"] for m in c.end_to_end}
    assert set(last["metrics"]) == names
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    tail = err.getvalue().strip().splitlines()[-len(c.limits):]
    assert all(line.startswith("check ") for line in tail)


THREE_TENANTS = dict(kind="three_tenants", chunks=[100000.0, 50000.0],
                     gap_s=2e-4, delay_s=1e-4, start_s=3e-4)


@pytest.fixture
def tenants(tmp_path, monkeypatch):
    """A configuration whose job kind lives only in ``tmp_path/jobs``,
    loaded through ``load_cell`` and cut to its own ``cpu_test``: three
    tenants on the 8-host leaf-spine, 4 lanes of 600 ticks on the XLA
    engine under the Table-1 sweep's limits.  The run's mix builds both
    sides from ``tmp_path``."""
    from lib import kinds
    from lib.deploy import program_inputs
    from reference.build import deployment

    base = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "fabrics", "jobs"):
        (base / d).mkdir(parents=True)
    shutil.copy(FIXTURE.parent / "three_tenants.py", base / "jobs")
    shutil.copy(BENCH / "fabrics" / "leaf_spine.py", base / "fabrics")
    shutil.copy(BENCH / "traffic" / "sweep18_kernel.json", base / "traffic")
    shutil.copy(BENCH / "limits" / "table1.sweep18.kernel.json",
                base / "limits" / "tenants.sweep.json")
    cfg = cells.load_json(BENCH / "configs" / "table1_leafspine32.json")
    cfg.update(job=THREE_TENANTS,
               cpu_test={"fabric": tiny.FABRIC, "job": THREE_TENANTS})
    (base / "configs" / "tenants.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tenants.sweep", "config": "tenants",
                       "traffic": "sweep18_kernel", "chips": 1}],
        "end_to_end": [{"name": "lane_ticks_per_s", "unit": "lane-ticks/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    c = cell("tenants.sweep", bench_file=tmp_path / "BENCHMARK.json",
             base=base)
    c.config["horizon_ticks"] = 600
    monkeypatch.setattr(kinds, "program_inputs",
                        functools.partial(program_inputs, base=base))
    monkeypatch.setattr(kinds, "deployment",
                        functools.partial(deployment, base=base))
    return c, base


def _run(c) -> dict:
    return harness.run(c, 2**31 + 5, 0.5, False, jax.devices()[:1], now(),
                       CompileClock(), out=io.StringIO(), err=io.StringIO())


def test_a_kind_found_by_name_builds_on_both_sides(tenants):
    c, base = tenants
    _same_deployment(c.config, base)
    res = _run(c)
    assert res["correct"] and res["check"]["int_mismatch"]["value"] == 0


def test_a_kind_found_by_name_sees_a_trigger_one_tick_late(tenants,
                                                           monkeypatch):
    """The control: the reference releases the triggered tenant one tick
    later than the program does."""
    from lib import kinds
    from reference.build import deployment

    c, base = tenants

    def late(cfg):
        dep = deployment(cfg, base)
        return dataclasses.replace(dep, trig_delay=dep.trig_delay + 1)

    monkeypatch.setattr(kinds, "deployment", late)
    res = _run(c)
    assert not res["correct"] and res["check"]["int_mismatch"]["value"] > 0
