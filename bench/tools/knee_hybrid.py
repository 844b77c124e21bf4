"""``bench/tools/knee.py`` for an LM cell of another kind: knee.py builds
its engine with ``bench.kinds.lm.setup``; this runs it with the ``setup``
of the kind the cell's configuration names (``lm_hybrid`` for
granite-h-int8-poisson), whose window and traffic are ``kinds/lm.py``'s.

    python3 bench/tools/knee_hybrid.py --workload granite-h-int8-poisson \\
        --rates 2 2.5 3 3.5 --seconds 51 --seed 5

Arguments and output lines are knee.py's.
"""
from __future__ import annotations

import argparse
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    args, _ = ap.parse_known_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.kinds import lm

    cell = harness.load_cell(args.workload)
    lm.setup = harness.load_module("kinds", cell.config["kind"]).setup
    runpy.run_path(os.path.join(ROOT, "bench", "tools", "knee.py"),
                   run_name="__main__")


if __name__ == "__main__":
    main()
