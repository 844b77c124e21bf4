#!/usr/bin/env python3
"""One run of one benchmark cell on the accelerator.

    python3 bench/run.py --workload lm-int8-poisson --seed 7 --seconds 30 \\
        --trace 0

Sets up the cell named in ``BENCHMARK.json`` (weights and traffic from
``--seed``, every program warmed), measures for ``--seconds``, checks the
served outputs against the plain reference, and prints one JSON line
last: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; then ``checks``,
each compared number with its limit.  It exits non-zero, printing no
result, without a TPU, with fewer chips than the cell asks for, or away
from the program's sources (``src/repro`` beside ``bench/``).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"[bench] refused: no program under {ROOT}/src/repro",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    try:
        return harness.main(args, T0)
    except harness.Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
