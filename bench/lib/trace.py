"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the newest ``.xplane.pb`` under a directory with JAX's own
``ProfileData``.  Device planes are the TPU cores (``/device:TPU:<n>``);
the operations that ran on one are the events of its ``XLA Ops`` line.
The harness's host spans (``jax.profiler.TraceAnnotation`` named
``bench.*``) lie on the host plane, on the same clock.  Everything is
clipped to the ``bench.window`` span that encloses the traced window.

The ops line holds control-flow operations (a ``while`` loop) as well as
the operations they run, nested inside them in time; only the leaves
count, or a loop would read as busy from its first to its last step.

* busy: the union of a chip's leaf operations; idle = window - busy;
* device time per operation (its HLO instruction name and opcode),
  summed over chips, with the instruction's text kept for matching;
* per host span name, the time inside such spans in which no chip was
  busy (the host's own share of that call);
* idle gaps of the first chip, each named by the innermost host span
  around its middle.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                              # mean over chips
    ops: dict = field(default_factory=dict)    # name -> seconds, all chips
    text: dict = field(default_factory=dict)   # name -> instruction text
    host_s: dict = field(default_factory=dict)  # span -> s with no chip busy
    span_count: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)   # (span, seconds), longest first

    def matching(self, pattern: str) -> float:
        """Seconds of the operations whose name or text holds ``pattern``."""
        return sum(s for n, s in self.ops.items()
                   if pattern in n or pattern in self.text.get(n, ""))


def union(intervals):
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(evs):
    """The events that hold no other event inside them; ``evs`` are
    ``(start, end, ...)`` tuples."""
    evs = sorted(evs, key=lambda e: (e[0], -e[1]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] < e[1] and nxt[1] <= e[1]:
            continue
        out.append(e)
    return out


def op_name(name: str) -> str:
    """``%fusion.12 fusion`` from an HLO instruction's text."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\}?\s([a-z][\w\-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def _overlap(intervals, s, e):
    """Seconds of ``intervals`` (sorted, disjoint) inside ``[s, e)``."""
    total = 0.0
    for a, b in intervals:
        if b <= s:
            continue
        if a >= e:
            break
        total += min(b, e) - max(a, s)
    return total


def reduce(planes, texts: dict, n_devices: int) -> Reduced:
    """``planes``: ``(plane name, [(line name, [(name, start_ns,
    end_ns)])])``, device operations named by :func:`op_name`, with
    ``texts`` holding each operation's instruction text; host spans from
    any non-device plane."""
    spans, dev_ops = [], {}
    for pname, lines in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", pname)
        for lname, evs in lines:
            if m and lname == OPS_LINE:
                dev_ops[int(m.group(1))] = evs
            elif not m:
                spans += [(s, e, n) for n, s, e in evs
                          if n.startswith(SPAN_PREFIX)]
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows or not dev_ops:
        raise ValueError("trace holds no bench.window span or no device ops")
    w0, w1 = windows[-1]
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in spans
             if n != WINDOW and e > w0 and s < w1]
    red = Reduced(window_s=(w1 - w0) / 1e9, busy_s=0.0)
    busy_by_dev, all_busy = [], []
    for dev in sorted(dev_ops)[:n_devices]:
        lv = leaves([(max(s, w0), min(e, w1), n)
                     for n, s, e in dev_ops[dev] if e > w0 and s < w1])
        for s, e, n in lv:
            red.ops[n] = red.ops.get(n, 0.0) + (e - s) / 1e9
            red.text.setdefault(n, texts.get(n, ""))
        busy = union((s, e) for s, e, _ in lv)
        busy_by_dev.append(busy)
        all_busy += busy
    red.busy_s = sum(sum(e - s for s, e in b) for b in busy_by_dev) \
        / len(busy_by_dev) / 1e9
    any_busy = union(all_busy)
    for s, e, n in spans:
        red.host_s[n] = red.host_s.get(n, 0.0) + \
            ((e - s) - _overlap(any_busy, s, e)) / 1e9
        red.span_count[n] = red.span_count.get(n, 0) + 1
    first = busy_by_dev[0]
    edges = [w0] + [x for iv in first for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            inner = [sp for sp in spans if sp[0] <= mid < sp[1]]
            name = max(inner, key=lambda sp: sp[0])[2] if inner else "none"
            red.gaps.append((name, (b - a) / 1e9))
    red.gaps.sort(key=lambda g: -g[1])
    return red


def read_planes(path: Path):
    """``(planes, texts)`` of one ``.xplane.pb`` as :func:`reduce` takes
    them: only device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out, texts = [], {}
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU:")
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = []
            for ev in line.events:
                name = ev.name
                if device:
                    key = op_name(name)
                    if key not in texts:
                        texts[key] = name
                    name = key
                elif not name.startswith(SPAN_PREFIX):
                    continue
                evs.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out, texts


def load(trace_dir: Path, n_devices: int) -> Reduced:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes, texts = read_planes(files[-1])
    return reduce(planes, texts, n_devices)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time (all chips) and the
    longest idle gaps of the first chip, by host span, in seconds."""
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.gaps[:top]]}
