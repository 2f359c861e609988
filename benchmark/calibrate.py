#!/usr/bin/env python3
"""The readings a cell's limits are set from (``PERF.md`` records them).

    python3 benchmark/calibrate.py --workload <name> --seeds 1,2,3 [--seconds 25]

For every seed, in one process, the cell's driver reads the program against
the plain reference (the lower reading), the reference computed in the
precision below the configuration's against itself (the control, which has
to come out as not correct) and the faults the cell can have, and prints one
JSON line. The benchmark's own runs never run this; it needs a TPU unless
``--rehearse-cpu``.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    args.seed, args.trace, args.keep_events = 0, 0, None
    cell, device, peaks = bench_run.open_cell(args)
    if cell is None:
        return 2
    run = bench_run.Run(cell, args, device, peaks)
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in cell.driver.calibrate(run, seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
