"""Readings for the limits of ``correct``: for each seed, one run of a
cell (the normal timed path, at the cell's load, for ``--seconds``) and,
on the same served outputs, the program's number and the control's.

    python3 bench/tools/control.py --workload lm-int8-poisson \\
        --seconds 12 --seeds 11 12 13

The control is the plain reference put in the program's place at the
next precision down (``check.control`` in the configuration: W8A8 rows
at W4A4, W4A4 rows at W3A3), compared with the reference as the
program's outputs are.  One JSON line per seed: the program's
reading, the control's, and the run's ``correct`` under the current
limits.  All seeds run in this one process (the chip is held once).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--traffic", default=None,
                    help="serve this mix (bench/traffic/<name>.json) in "
                         "place of the cell's own, e.g. for a witness run")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    cell = harness.load_cell(args.workload)
    if args.traffic:
        cell.traffic = harness.load_json(os.path.join(
            harness.BENCH, "traffic", args.traffic + ".json"))
    devs = harness.devices(cell.chips, require_tpu=True)
    harness.enable_compile_cache()
    kind = harness.load_module("kinds", cell.config["kind"])
    counter = harness.CompileCounter()
    for seed in args.seeds:
        ctx = harness.Context(cell, seed, args.seconds, False, devs, None,
                              time.perf_counter(), counter)
        run = kind.run(ctx)
        r = kind.compare(ctx, *run.compare_args, with_control=True)
        print(json.dumps({"seed": seed, "correct": run.correct,
                          "attempted": run.attempted, "failed": run.failed,
                          "setup_s": run.setup_s,
                          "peak": run.memory_peak_bytes, **r}), flush=True)
        del run
        gc.collect()


if __name__ == "__main__":
    main()
