"""The reduction of the program's own spans and scopes, and the readers
of the metrics built on it, on two thinned traces recorded on the chip:
two ``SimController.step()`` calls of ``table1.online.kernel``, and the
start of one 512-host ``simulate_grid`` dispatch through a device stall.
A trace of a program without spans or scopes leaves every reader
silent."""
from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from lib import cells, program_trace as pt, trace as tr
from tiny import BENCH

FIXTURES = BENCH / "tests" / "fixtures"
NEW = ("controller.enqueue_ms_per_step", "controller.readback_ms_per_step",
       "grid.statics_ms_per_dispatch", "engine.instance_view_us_per_lane_tick",
       "engine.marking_us_per_lane_tick", "engine.unscoped_us_per_lane_tick",
       "device.idle_unnamed_pct.sweep", "device.idle_unnamed_pct.online")


def _recorded(name):
    """``(program reduction, benchmark reduction)`` of a fixture; ops are
    named by their whole HLO text, as in a trace."""
    doc = json.loads((FIXTURES / name).read_text())
    text = doc["texts"]
    planes = [(p, [(ln, [(text.get(n, n), s, e) for n, s, e in evs])
                   for ln, evs in lines]) for p, lines in doc["planes"]]
    paths = {text[k]: v for k, v in doc.get("paths", {}).items()}
    red = tr.reduce(doc["planes"], text, 1)
    return pt.reduce(planes, paths, 1), red


def _read(monkeypatch, prog, red, lane_ticks=1):
    monkeypatch.setattr(pt, "of", lambda ctx: prog)
    ctx = SimpleNamespace(red=red, lane_ticks=lane_ticks)
    return {m: cells.metric_reader(m)(ctx) for m in NEW + (
        "controller.host_ms_per_step", "grid.host_ms_per_dispatch",
        "engine.xla_us_per_lane_tick", "kernel.netsim_tick_us_per_lane_tick",
        "device.idle_pct.sweep")}


def _segments(doc):
    """Window and unnamed idle time of a fixture by elementary segments
    between all event edges: leaves found pairwise among the operations
    of positive duration, spans the program's."""
    ev = {p: ls for p, ls in doc["planes"]}
    host = [e for _, es in ev["/host:CPU"] for e in es]
    w0, w1 = [(s, e) for n, s, e in host if n == "bench.window"][-1]
    ops = [(max(s, w0), min(e, w1)) for _, es in ev["/device:TPU:0"]
           for _, s, e in es if e > w0 and s < w1 and e > s]
    leaves = [a for a in ops if not any(
        b != a and a[0] <= b[0] and b[1] <= a[1] for b in ops)]
    spans = [(s, e) for n, s, e in host if n.startswith("netsim.")]
    edges = sorted({w0, w1} | {x for iv in ops + spans for x in iv})
    unnamed = 0.0
    for a, b in zip(edges, edges[1:]):
        inside = lambda ivs: any(s <= a and b <= e for s, e in ivs)
        if not inside(leaves) and not inside(spans):
            unnamed += b - a
    return (w1 - w0) / 1e9, unnamed / 1e9


def test_online_split_of_the_host_time(monkeypatch):
    prog, red = _recorded("online_program_trace.json")
    got = _read(monkeypatch, prog, red)
    host = got["controller.host_ms_per_step"]
    enq = got["controller.enqueue_ms_per_step"]
    back = got["controller.readback_ms_per_step"]
    assert prog.count["netsim.step"] == red.span_count["bench.step"] == 2
    assert 0 < enq <= host and 0 < back <= host
    # the rest of a step's host time is its own bookkeeping around the
    # spans (run_window's checks), well under the 2 ms the split allows
    assert 0 <= host - (enq + back) < 2.0
    window, unnamed = _segments(json.loads(
        (FIXTURES / "online_program_trace.json").read_text()))
    assert prog.window_s == pytest.approx(window)
    assert prog.idle_unnamed_s == pytest.approx(unnamed)
    assert got["device.idle_unnamed_pct.online"] == pytest.approx(
        100 * unnamed / window)
    assert got["grid.statics_ms_per_dispatch"] is None


def test_sweep_device_time_partition_and_the_stall(monkeypatch):
    """The 51.5 ms "stall" of the 512-host tick loop is a kernel call that
    ``trace.leaves`` drops: a marker of no duration starts with it."""
    prog, red = _recorded("sweep_program_trace.json")
    got = _read(monkeypatch, prog, red, lane_ticks=8)
    total = sum(prog.ops.values())
    parts = [prog.scope_s(s) for s in prog.scoped]
    assert sum(parts) + prog.unscoped_s() + prog.kernel_s() == \
        pytest.approx(total)
    table = pt.stages(prog)
    assert set(table) == prog.scoped | {"kernel", "unscoped"}
    assert sum(g["s"] for g in table.values()) == pytest.approx(total)
    assert table["kernel"]["ops"][0][0].startswith("%netsim_tick.")
    assert {"netsim.marking", "netsim.instance_view",
            "netsim.kernel_operands"} <= prog.scoped
    # the stages and the unscoped ops are the time of every operation but
    # the kernel, as the benchmark's reduction reads it
    assert 1e6 * (sum(parts) + prog.unscoped_s()) / 8 == pytest.approx(
        got["engine.xla_us_per_lane_tick"], rel=0.01)
    assert got["engine.marking_us_per_lane_tick"] == pytest.approx(
        1e6 * prog.scope_s("netsim.marking") / 8)
    # one kernel call more than trace.py counts, and it is the stall
    dropped = prog.kernel_s() - red.matching(pt.KERNEL)
    assert dropped == pytest.approx(0.0515, abs=1e-4)
    assert red.window_s - red.busy_s > dropped
    assert prog.idle_unnamed_s < 0.1 * dropped
    # set-up on the host is named and is far less than the 51.5 ms
    assert prog.count == {"netsim.grid": 1, "netsim.grid.statics": 1,
                          "netsim.grid.launch": 1}
    assert 0 < got["grid.statics_ms_per_dispatch"] < 51.5 < \
        got["grid.host_ms_per_dispatch"]
    window, unnamed = _segments(json.loads(
        (FIXTURES / "sweep_program_trace.json").read_text()))
    assert got["device.idle_unnamed_pct.sweep"] == pytest.approx(
        100 * unnamed / window)
    assert got["device.idle_unnamed_pct.sweep"] < \
        got["device.idle_pct.sweep"]


def test_readers_are_silent_without_program_spans(monkeypatch):
    """A trace of a program that opens no ``netsim.*`` span and lowers no
    scope (the benchmark's first recorded trace) reads as nothing."""
    doc = json.loads((FIXTURES / "online_trace.json").read_text())
    prog = pt.reduce(doc["planes"], {}, 1)
    red = tr.reduce(doc["planes"], doc["texts"], 1)
    assert not prog.count and not prog.scoped
    got = _read(monkeypatch, prog, red, lane_ticks=320)
    assert all(got[m] is None for m in NEW)
    assert got["controller.host_ms_per_step"] is not None


@pytest.mark.parametrize("path, scope", [
    ("jit(_grid_impl)/vmap()/while/body/closed_call/while/body/"
     "closed_call/netsim.marking/gather:", "netsim.marking"),
    ("jit(f)/netsim.kernel_operands/netsim_tick/while/body/netsim.share/"
     "sin:", "netsim.share"),
    ("jit(f)/netsim.kernel_operands/netsim_tick/pallas_call:",
     "netsim.kernel_operands"),
    ("jit(_grid_impl)/vmap()/while/body/closed_call/while:", None),
    ("jit(f)/netsim_tick/pallas_call:", None),
    (None, None),
])
def test_scope_of_a_path(path, scope):
    assert pt.scope_of(path) == scope


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """Protobuf wire bytes of ``(field, int | bytes | str)`` pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_scope_paths_from_event_metadata(tmp_path):
    """The scope path is the ``tf_op`` stat of an op's event metadata,
    held as a string or as a reference to a stat metadata's name."""
    stat_md = [(5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op"))))),
               (5, _msg((1, 9), (2, _msg(
                   (1, 9), (2, "jit(g)/netsim.queues/max:"))))),
               (5, _msg((1, 3), (2, _msg((1, 3), (2, "flops")))))]
    ev_md = [(4, _msg((1, 1), (2, _msg(
                 (1, 1), (2, "%fusion.1 = f32[4] fusion(...)"),
                 (5, _msg((1, 3), (3, 12))),
                 (5, _msg((1, 7), (5, "jit(g)/while/netsim.marking/mul:"))))))),
             (4, _msg((1, 2), (2, _msg(
                 (1, 2), (2, "%max.2 = f32[4] maximum(...)"),
                 (5, _msg((1, 7), (7, 9))))))),
             (4, _msg((1, 3), (2, _msg((1, 3), (2, "%copy.3 = copy(...)")))))]
    lines = [(3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 5)))))]
    device = _msg((1, 1), (2, "/device:TPU:0"), *lines, *ev_md, *stat_md)
    host = _msg((1, 2), (2, "/host:CPU"), (4, _msg((1, 1), (2, _msg(
        (1, 1), (2, "netsim.step"),
        (5, _msg((1, 7), (5, "jit(h)/netsim.starts/x:"))))))), *stat_md)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    assert pt.scope_paths(path) == {
        "%fusion.1 = f32[4] fusion(...)": "jit(g)/while/netsim.marking/mul:",
        "%max.2 = f32[4] maximum(...)": "jit(g)/netsim.queues/max:"}
