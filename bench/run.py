"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; see ``bench/lib/harness.py`` for what a run does and
prints.  Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from lib.cells import load_cell
    from lib.chip import CompileClock, NoChip, enable_compile_cache, \
        tpu_devices

    cell = load_cell(args.workload)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = CompileClock()
    from lib.harness import run

    run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START,
        clock)
    return 0


if __name__ == "__main__":
    sys.exit(main())
