"""Readings of the control and of planted faults, on the chip, at a cell's
own size.

    python3 benchmarks/chip/chipbench/control.py --workload <cell> \
        --fault <float32_keys|half_batch|...> --seeds 1,2,3 --seconds 15

One process: the fault is planted in the timed path (``selftest.plant``),
then one whole run of the cell per seed, each with its window, reference
and comparison, each printing its result line.  The ``float32_keys``
control is the fused step with its argmin keys in float32, the precision
below the float64 the configurations state; a sound limit is one it fails.
A benchmark run never calls this.
"""
from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, registry, selftest
    cell = registry.resolve_cell(args.workload)
    selftest.plant(args.fault)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        print(f"control {args.workload} fault {args.fault} seed {seed}",
              flush=True)
        harness.run_cell(cell, seed, args.seconds, False, t_start)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
