"""Readings for the limits of a cell's check, in one process on the chip.

    python3 bench/tests/readings.py --workload <cell> --seeds 1 2 3 \\
        --control-seeds 1 2 3 [--seconds 30]

For each seed it drives the cell's timed path as a run does (one
dispatch of a sweep; a window of ``--seconds`` of steps online), and
prints one JSON line: the program's numbers against the reference and,
for the control seeds, the control's: the reference computed in
bfloat16, one precision below the float32 the configuration states, put
in the program's place.  The limits in ``limits/<cell>.json`` lie
between the largest program reading and the smallest control reading.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
    import jax.numpy as jnp
    from lib.cells import load_cell
    from lib.chip import enable_compile_cache, tpu_devices
    from lib.kinds import KINDS

    cell = load_cell(args.workload)
    tpu_devices(cell.chips)
    enable_compile_cache()
    kind = cell.traffic["kind"]
    for i, seed in enumerate(args.seeds):
        d = KINDS[kind](cell, seed)
        if i == 0:
            d.warm_up()
        d.window(args.seconds, 1 if kind == "sweep" else None)
        d.take_outputs()
        line = {"cell": cell.name, "seed": seed, "units": len(d.walls),
                "program": d.readings()}
        if seed in args.control_seeds:
            line["control"] = d.control_readings(jnp.bfloat16)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
