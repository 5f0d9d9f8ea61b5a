"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call = scenario wall
time; derived = the headline metric next to the paper's target).

Set BENCH_QUICK=1 for reduced seeds/horizons; results cache in
benchmarks/.cache.json so repeated invocations are cheap.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    import importlib

    from .common import enable_compile_cache
    enable_compile_cache()
    specs = [
        ("fig2_misalignment",
         lambda r: f"baseline_overlap={r['baseline_ecmp']['max_overlap']};"
                   f"infl={r['baseline_ecmp'].get('cct_inflation')}",
         "paper: overlap~30 +60%CCT"),
        ("fig4_mitigation",
         lambda r: f"overlap {r['baseline']['overlap_max']}->"
                   f"{r['symphony']['overlap_max']};"
                   f"cct_red={r.get('cct_reduction')}",
         "paper: 24-35 -> 3-6 | ~30%"),
        ("fig5_cct_cdf",
         lambda r: f"vs_base={r.get('reduction_vs_baseline')};"
                   f"vs_pq={r.get('reduction_vs_pq')}",
         "paper: ~22% | ~19%"),
        ("table2_e2e",
         lambda r: ";".join(f"{k}={v['improvement']}"
                            for k, v in r.items()
                            if isinstance(v, dict) and "improvement" in v),
         "paper: vgg .50-.54 resnet .21-.24 transformer ~0"),
        ("fig6_commratio",
         lambda r: ";".join(f"{k}={v['normalized_jct']}"
                            for k, v in r.items() if isinstance(v, dict)),
         "paper: ->~0.7 @64x"),
        ("fig7_multitenant",
         lambda r: f"span_red={r.get('span_reduction')};" +
                   ";".join(f"{k}={v.get('jct_improvement')}"
                            for k, v in r.items() if k.startswith('scale_')),
         "paper: .015@16 -> ~.17@64"),
        ("fig8_sweeps",
         lambda r: ";".join(f"{k}={list(v.values())[0]}"
                            for k, v in r.items() if isinstance(v, dict)),
         "paper: grows w/ imbalance+chunk; k sweet 1e-3..1e-2"),
        ("fig9_two_flow",
         lambda r: ";".join(
             f"{k}:A-{v['A_reduction']}/B+{v['B_cost']}"
             for k, v in r.items() if isinstance(v, dict)),
         "paper: A -.12 B +.02 @0.5s"),
        ("netsim_perf",
         lambda r: f"ticks/s={r['ticks_per_s_single']};"
                   f"vmap8_speedup={r['vmap_speedup']}",
         "sim throughput"),
    ]
    print("name,us_per_call,derived")
    for name, extract, note in specs:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            r = mod.bench()
            wall = r.get("_wall_s", 0.0)
            print(f"{name},{wall * 1e6:.0f},{extract(r)} [{note}]")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}")
        sys.stdout.flush()
    # roofline table from the dry-run artifacts (no simulation)
    try:
        from . import roofline
        for row in roofline.rows("single"):
            if "skipped" in row:
                print(f"roofline.{row['cell']},0,skipped:{row['skipped'][:50]}")
            elif "error" in row:
                print(f"roofline.{row['cell']},0,ERROR:{row['error']}")
            else:
                print(f"roofline.{row['cell']},0,"
                      f"bottleneck={row['bottleneck']};"
                      f"tC={row['t_compute_ms']}ms;tM={row['t_memory_ms']}ms;"
                      f"tX={row['t_collective_ms']}ms;"
                      f"useful={row['useful_ratio']}")
    except FileNotFoundError:
        print("roofline,nan,run `python -m repro.launch.dryrun` first")


if __name__ == "__main__":
    main()
