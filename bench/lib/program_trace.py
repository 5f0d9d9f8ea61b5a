"""Reduction of a profiler trace to the simulator's own spans and scopes.

The program opens host spans named ``netsim.*``
(``jax.profiler.TraceAnnotation`` in ``SimController.step``,
``run_window`` and ``simulate_grid``) and lowers each stage of the tick
under a ``jax.named_scope`` named ``netsim.<stage>``.  This module reads
both from the newest ``.xplane.pb`` under the harness's trace directory,
once per file for all readers, clipped to the ``bench.window`` span, with
``trace.py``'s leaf rule and busy time (but see below):

* per ``netsim.*`` span name, the count of spans and the seconds inside
  them in which no chip was busy;
* per device leaf operation, its seconds summed over chips, whether it
  is the fused kernel (its Mosaic custom-call target, as
  ``kernel.netsim_tick_us_per_lane_tick`` matches it), and its innermost
  ``netsim.`` scope, read from the scope path (the HLO ``op_name``)
  that the trace keeps in the ``tf_op`` stat of the op's event metadata;
  an op whose path names no ``netsim.`` scope is unscoped;
* the window time in which no chip was busy and no ``netsim.*`` span
  was open.

Leaves are found among the operations of positive duration.  The ops
line also holds markers of no duration (``ConcatBitcast`` and other
custom calls), and one that starts at the same nanosecond as another
operation makes ``trace.leaves`` take that operation for a loop around
it and drop it: at 512 hosts a fifth of the kernel calls, 51.5 ms each,
read as idle there.  With the markers left out only the ``while`` loops
hold other operations.

A program without these spans or scopes reads as empty: ``count`` and
``scoped`` stay empty, and the readers return nothing.  So does an
executable that JAX's persistent compilation cache hands back from a
program without them: the cache key leaves out the HLO metadata, so a
program that differs only in its scopes (the sharded XLA grid) finds the
old executable, whose ops carry the old paths.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

from .trace import OPS_LINE, WINDOW, _overlap, leaves, op_name, union

PREFIX = "netsim."
KERNEL = 'custom_call_target="tpu_custom_call"'
SCOPE_STAT = "tf_op"        # the op's scope path, on its event metadata
_SCOPE = re.compile(r"(?:^|/)(netsim\.[A-Za-z_]+)(?=[/:]|$)")


def scope_of(path: str | None) -> str | None:
    """The innermost ``netsim.`` component of an op's scope path, such as
    ``jit(_grid_impl)/vmap()/while/body/netsim.marking/gather:``; ``None``
    where the path names none."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else None


@dataclass
class Program:
    window_s: float
    host_s: dict = field(default_factory=dict)   # span -> s, no chip busy
    count: dict = field(default_factory=dict)    # span -> spans
    ops: dict = field(default_factory=dict)      # op -> s, all chips
    scope: dict = field(default_factory=dict)    # op -> scope or None
    kernel: set = field(default_factory=set)     # ops that are the kernel
    idle_unnamed_s: float = 0.0

    @property
    def scoped(self) -> set:
        """The scopes any op of the window carries."""
        return {s for s in self.scope.values() if s}

    def scope_s(self, name: str) -> float:
        """Device seconds of the ops in scope ``name`` (never the kernel)."""
        return sum(s for op, s in self.ops.items()
                   if op not in self.kernel and self.scope.get(op) == name)

    def unscoped_s(self) -> float:
        """Device seconds of the ops that are neither the kernel nor in any
        ``netsim.`` scope."""
        return sum(s for op, s in self.ops.items()
                   if op not in self.kernel and not self.scope.get(op))

    def kernel_s(self) -> float:
        return sum(self.ops[op] for op in self.kernel if op in self.ops)

    def per_span(self, names, per: str) -> float | None:
        """Host-only seconds in the spans ``names`` over the count of
        ``per`` spans; ``None`` where no ``per`` span was traced."""
        n = self.count.get(per, 0)
        if not n:
            return None
        return sum(self.host_s.get(name, 0.0) for name in names) / n


def reduce(planes, paths: dict, n_devices: int) -> Program:
    """``planes`` as ``trace.reduce`` takes them, device operations named
    by their whole event name (the op's HLO text); ``paths`` maps such a
    name to its scope path."""
    spans, dev_ops = [], {}
    for pname, lines in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", pname)
        for lname, evs in lines:
            if m and lname == OPS_LINE:
                dev_ops[int(m.group(1))] = evs
            elif not m:
                spans += [(s, e, n) for n, s, e in evs
                          if n == WINDOW or n.startswith(PREFIX)]
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows or not dev_ops:
        raise ValueError("trace holds no bench.window span or no device ops")
    w0, w1 = windows[-1]
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in spans
             if n != WINDOW and e > w0 and s < w1]
    prog = Program(window_s=(w1 - w0) / 1e9)
    all_busy = []
    for dev in sorted(dev_ops)[:n_devices]:
        lv = leaves([(max(s, w0), min(e, w1), n)
                     for n, s, e in dev_ops[dev] if e > w0 and s < w1
                     and e > s])
        for s, e, n in lv:
            prog.ops[n] = prog.ops.get(n, 0.0) + (e - s) / 1e9
            if n not in prog.scope:
                prog.scope[n] = scope_of(paths.get(n))
                if KERNEL in n:
                    prog.kernel.add(n)
        all_busy += [(s, e) for s, e, _ in lv]
    any_busy = union(all_busy)
    for s, e, n in spans:
        prog.host_s[n] = prog.host_s.get(n, 0.0) + \
            ((e - s) - _overlap(any_busy, s, e)) / 1e9
        prog.count[n] = prog.count.get(n, 0) + 1
    named = union(any_busy + [(s, e) for s, e, _ in spans])
    prog.idle_unnamed_s = ((w1 - w0) - sum(e - s for s, e in named)) / 1e9
    return prog


def read_planes(path: Path) -> list:
    """The planes of one ``.xplane.pb`` as :func:`reduce` takes them:
    device operations, the window and the program's host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith("/device:TPU:")
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            lines.append((line.name, [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in line.events
                if device or ev.name == WINDOW or ev.name.startswith(PREFIX)]))
        out.append((plane.name, lines))
    return out


# -- the scope paths: a stat of each op's event metadata, which
# ``ProfileData`` does not expose, read from the protobuf wire format of
# ``XSpace`` (planes 1; a plane's name 2, event_metadata 4 and
# stat_metadata 5, maps with key 1 and value 2; XEventMetadata name 2,
# stats 5; XStatMetadata name 2; XStat metadata_id 1, str_value 5,
# ref_value 7).
def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, pos=0, end=None):
    """``(field, value)`` of one message: a varint as an int, a
    length-delimited field as a memoryview."""
    end = len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            v, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            v, pos = buf[pos:pos + n], pos + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {kind} not handled")
        yield key >> 3, v


def _map_values(entries):
    for entry in entries:
        yield next((v for f, v in _fields(entry) if f == 2), b"")


def scope_paths(path: Path) -> dict:
    """``{op's HLO text: scope path}`` for the operations of every TPU
    plane of one ``.xplane.pb``."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        parts = {2: [], 4: [], 5: []}
        for g, v in _fields(plane):
            if g in parts:
                parts[g].append(v)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for md in _map_values(parts[5]):
            d = dict(_fields(md))
            stat_names[d.get(1, 0)] = bytes(d.get(2, b"")).decode()
        for md in _map_values(parts[4]):
            op, scope = None, None
            for g, v in _fields(md):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != SCOPE_STAT:
                        continue
                    scope = bytes(st[5]).decode() if 5 in st else \
                        stat_names.get(st.get(7), "")
            if op is not None and scope:
                out[op] = scope
    return out


_CACHE: dict = {}


def load(trace_dir: Path, n_devices: int) -> Program:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``; the result is
    kept for every later reader of the same file."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    st = files[-1].stat()
    key = (str(files[-1]), st.st_mtime_ns, st.st_size, n_devices)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(read_planes(files[-1]),
                             scope_paths(files[-1]), n_devices)
    return _CACHE[key]


def of(ctx) -> Program:
    """The program's reduction of the traced run a metric reads."""
    from .harness import TRACE_DIR

    return load(TRACE_DIR, ctx.cell.chips)


def stages(prog: Program, top: int = 5) -> dict:
    """Device seconds per scope (and of the kernel and the unscoped ops),
    each with its largest operations as ``[label, seconds]``, where the
    label is the op's HLO name and opcode (``%fusion.12 fusion``)."""
    groups: dict = {}
    for op, s in prog.ops.items():
        g = "kernel" if op in prog.kernel else prog.scope.get(op) or "unscoped"
        groups.setdefault(g, []).append((op_name(op), s))
    return {g: {"s": sum(s for _, s in ops),
                "ops": sorted(ops, key=lambda o: -o[1])[:top]}
            for g, ops in groups.items()}
