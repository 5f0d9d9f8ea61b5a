import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) cell
on the production meshes, record memory/cost analysis + collective bytes.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --out results.json

Results are cached per (arch, shape, mesh) in the output JSON; finished cells
are skipped on re-run (resumable).
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import numpy as np

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "dryrun_results.json"

# v5e hardware constants (roofline)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link (~per direction)
HBM_BYTES = 16 * 1024**3


def collective_bytes(hlo: str) -> dict:
    """Sum operand bytes of collective ops in compiled HLO, grouped by kind,
    with ring-cost wire-byte estimates per chip."""
    DT = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3fn": 1,
          "f8e5m2": 1, "s16": 2, "u16": 2, "c64": 8}
    out: dict[str, dict] = {}
    # result type(s) appear right after '=' for the collective op
    pat = re.compile(
        r"= ((?:\(?)(?:[a-z0-9]+\[[0-9,]*\][^ )]*(?:, )?)+\)?) "
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
        r"(-start|-done)?\(")
    grp = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
    grp_iota = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m or (m.group(3) == "-done"):
            continue
        kind = m.group(2)
        tensors = re.findall(r"([a-z0-9]+)\[([0-9,]*)\]", m.group(1))
        nbytes = 0
        for dt, dims in tensors:
            sz = 1
            for d in dims.split(","):
                if d:
                    sz *= int(d)
            nbytes += sz * DT.get(dt, 4)
        # group size for ring cost factors
        n = None
        g = grp.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            g = grp_iota.search(line)
            if g:
                n = int(g.group(2))
        n = n or 1
        if kind == "all-reduce":
            wire = 2 * nbytes * (n - 1) / max(n, 1)
        elif kind in ("all-gather",):
            wire = nbytes * (n - 1) / max(n, 1)   # nbytes = result (gathered)
        elif kind in ("reduce-scatter", "all-to-all"):
            wire = nbytes * (n - 1) / max(n, 1)
        else:  # collective-permute
            wire = nbytes
        d = out.setdefault(kind, {"count": 0, "bytes": 0.0, "wire": 0.0})
        d["count"] += 1
        d["bytes"] += float(nbytes)
        d["wire"] += float(wire)
    return out


def _full_params(cfg):
    from ..models import build_model
    from ..models.params import count_params
    from .steps import active_param_count
    n = count_params(build_model(cfg).param_spec())
    return n, active_param_count(cfg, n)


def _measure(arch, shape_name, mesh, overrides, depth):
    from .steps import build_cell
    cell = build_cell(arch, shape_name, mesh, policy_overrides=overrides,
                      depth_override=depth)
    with mesh:
        jitted = jax.jit(cell.fn, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    return cell, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             keep_hlo: bool = False, roofline: bool = False) -> dict:
    """Lower + compile one cell.

    roofline=True measures a loop-free variant (unrolled layers, accum=1,
    unchunked CE/attention/SSD/MoE) because XLA cost analysis counts
    while-loop bodies once.  To keep unrolled compiles tractable, costs are
    measured at depths of 1 and 2 layer-groups and extrapolated with the
    exact linear model cost(G) = c + d*G (stacks are homogeneous per group;
    optimizer/param-proportional terms are linear in G too).  Memory
    analysis always comes from the production (scanned) lowering.
    """
    from .. import flags
    from ..configs import registry as _reg
    from .mesh import make_production_mesh
    from .steps import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if roofline:
        flags.set_roofline(True)
        try:
            cfg = _reg.get_config(arch)
            from ..models import build_model
            model = build_model(cfg)
            period = getattr(model, "period", 1)
            G = cfg.num_layers // period if period else cfg.num_layers
            overrides = {"scan_layers": False, "accum": 1}
            cell, c1 = _measure(arch, shape_name, mesh, overrides, period)
            _, c2 = _measure(arch, shape_name, mesh, overrides, 2 * period)

            def costs(comp):
                ca = comp.cost_analysis() or {}
                colls = collective_bytes(comp.as_text())
                return (float(ca.get("flops", 0.0)),
                        float(ca.get("bytes accessed", 0.0)),
                        sum(d["wire"] for d in colls.values()), colls)

            f1, b1, w1, _ = costs(c1)
            f2, b2, w2, colls2 = costs(c2)

            def extrap(v1, v2):
                # exact linear model; if XLA restructured ops between depths
                # (slope <= 0), fall back to proportional scaling from the
                # 2-group measurement.
                if v2 > v1 > 0:
                    return v1 + (v2 - v1) * (G - 1)
                return v2 / 2.0 * G

            flops_dev = extrap(f1, f2)
            bytes_dev = extrap(b1, b2)
            wire_dev = extrap(w1, w2)
            t_all = time.time() - t0
            return {
                "arch": arch, "shape": shape_name,
                "mesh": list(mesh.devices.shape), "chips": mesh.size,
                "lower_s": 0.0, "compile_s": round(t_all, 1),
                "flops_per_device": flops_dev,
                "bytes_per_device": bytes_dev,
                "wire_bytes_per_device": wire_dev,
                "collectives": colls2,
                "extrapolated": {"groups": G, "period": period,
                                 "g1": [f1, b1, w1], "g2": [f2, b2, w2]},
                "memory": {"argument": 0, "output": 0, "alias": 0, "temp": 0,
                           "per_device_total": 0, "fits_v5e": True,
                           "note": "see production lowering record"},
                "model_params": _full_params(cfg)[0],
                "active_params": _full_params(cfg)[1],
                "t_compute": flops_dev / PEAK_FLOPS,
                "t_memory": bytes_dev / HBM_BW,
                "t_collective": wire_dev / ICI_BW,
                "ok": True,
            }
        finally:
            flags.set_roofline(False)

    cell = build_cell(arch, shape_name, mesh)
    with mesh:
        jitted = jax.jit(cell.fn, donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    colls = collective_bytes(hlo)
    n_chips = mesh.size
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    wire_dev = sum(d["wire"] for d in colls.values())
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": colls,
        "wire_bytes_per_device": wire_dev,
        "memory": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "per_device_total": int(per_dev_bytes),
            "fits_v5e": bool(per_dev_bytes <= HBM_BYTES),
        },
        "model_params": cell.model_params,
        "active_params": cell.active_params,
        # roofline terms (seconds) — see EXPERIMENTS.md §Roofline
        "t_compute": flops_dev / PEAK_FLOPS,
        "t_memory": bytes_dev / HBM_BW,
        "t_collective": wire_dev / ICI_BW,
        "ok": True,
    }
    if keep_hlo:
        res["hlo_len"] = len(hlo)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--roofline", action="store_true",
                    help="loop-free lowering for exact cost analysis "
                         "(single-pod; stored under key suffix /roofline)")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    if args.roofline:
        args.mesh = "single"

    from ..configs import registry

    out_path = Path(args.out)
    results = json.loads(out_path.read_text()) if out_path.exists() else {}

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    for arch, spec, skip in registry.all_cells():
        if args.arch and registry.canonical(args.arch) != arch:
            continue
        if args.shape and spec.name != args.shape:
            continue
        cells.append((arch, spec, skip))

    for arch, spec, skip in cells:
        for mp in meshes:
            mesh_name = "multi" if mp else "single"
            if args.roofline:
                mesh_name = "roofline"
            key = f"{arch}/{spec.name}/{mesh_name}"
            if skip:
                results[key] = {"arch": arch, "shape": spec.name,
                                "skipped": skip, "ok": True}
                print(f"[skip] {key}: {skip}")
                continue
            if key in results and results[key].get("ok") and not args.force:
                print(f"[cached] {key}")
                continue
            print(f"[run] {key} ...", flush=True)
            try:
                res = run_cell(arch, spec.name, mp, roofline=args.roofline)
                print(f"  ok: compile={res['compile_s']}s "
                      f"mem/dev={res['memory']['per_device_total']/2**30:.2f}GiB "
                      f"t_comp={res['t_compute']*1e3:.2f}ms "
                      f"t_mem={res['t_memory']*1e3:.2f}ms "
                      f"t_coll={res['t_collective']*1e3:.2f}ms", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                res = {"arch": arch, "shape": spec.name, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {res['error'][:200]}", flush=True)
            results[key] = res
            out_path.write_text(json.dumps(results, indent=1))
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells ok -> {out_path}")


if __name__ == "__main__":
    main()
