"""Cells of the benchmark, found by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Everything that belongs to one of them lies in files of its own,
found by name, so a later cell, configuration or metric is added as
files and entries alone:

* ``configs/<config>.json``: the deployment (fabric, job, engine
  constants); its ``fabric`` and ``job`` sections each name a ``kind``;
* ``fabrics/<kind>.py``, ``jobs/<kind>.py``: the constructors of one
  fabric or job kind, ``program(...)`` through the simulator's public
  constructors (imported inside the function) and ``reference(...)``
  with numpy alone, so the two sides share no code;
* ``traffic/<traffic>.json``: the mix's parameters (traffic kind, knob
  axes, ticks per dispatch or window, engine path, chips, policy);
* ``policies/<policy>.py``: an online action stream a mix names;
* ``limits/<cell>.json``: the limit of each number the check compares;
* ``metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file of the benchmark by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              base: Path = BENCH) -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}; "
                       f"have {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(base / "configs" / f"{w['config']}.json"),
        traffic=load_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(base / "limits" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def kind(section: str, spec: dict, base: Path = BENCH):
    """``(module, arguments)`` of a configuration's ``fabric`` or ``job``
    section: the file ``<section>/<kind>.py`` and the section's other
    keys."""
    args = dict(spec)
    name = args.pop("kind")
    path = base / section / f"{name}.py"
    return load_module(path, f"{section}.{name}"), args


def metric_reader(name: str, base: Path = BENCH):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return load_module(base / "metrics" / f"{name}.py", name).read


def policy(name: str, base: Path = BENCH):
    """The ``Policy`` class of ``policies/<name>.py``."""
    return load_module(base / "policies" / f"{name}.py", name).Policy
