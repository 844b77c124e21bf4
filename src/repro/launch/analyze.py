"""Static-analysis CLI (DESIGN.md §12): the four-pass auditor suite.

    PYTHONPATH=src python -m repro.launch.analyze --all

runs the AST linter (host-sync / nondeterminism / RNG / static-bit
rules over the registered hot paths), the retrace auditor (one abstract
signature per compiled entrypoint across every config × budget × k ×
(start, length) variant), the sharding checker (every pspec divides
every 1/2/4/8-device mesh for all ten FULL configs), and the ledger
auditor (every ``CostRecord`` field written in ``serve/`` is consumed
by ``aggregate()`` or waived).  Exit status 0 iff no fresh findings and
no stale baseline entries — the blocking CI ``analysis`` job rides
on it.

``--json PATH`` writes the machine-readable result (the CI
``analysis`` job uploads it as its status artifact).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro import analysis


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.launch.analyze",
        description="static retrace/host-sync/sharding/ledger auditors")
    p.add_argument("--all", action="store_true",
                   help="run every pass (same as naming all four)")
    for name in analysis.ALL_PASSES:
        p.add_argument(f"--{name}", action="store_true",
                       help=f"run the {name} pass")
    p.add_argument("--configs", nargs="*", default=None, metavar="ARCH",
                   help="restrict retrace/sharding to these arch ids")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the machine-readable suite result here")
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help="override the checked-in baseline file")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    passes = [n for n in analysis.ALL_PASSES if getattr(args, n)]
    if args.all or not passes:
        passes = list(analysis.ALL_PASSES)

    t0 = time.time()
    res = analysis.run_suite(passes, arch_ids=args.configs,
                             baseline_path=args.baseline)
    dt = time.time() - t0

    for pr in res.passes:
        status = "ok" if pr.ok else f"{len(pr.fresh)} finding(s)"
        extra = f" ({pr.notes[0]})" if pr.notes else ""
        print(f"[{pr.name}] {status}{extra}")
        for f in pr.fresh:
            print("  " + f.render().replace("\n", "\n  "))
        if pr.suppressed:
            print(f"  {len(pr.suppressed)} finding(s) suppressed by "
                  f"baseline")
    for e in res.stale_baseline:
        print(f"[baseline] STALE entry {e['rule']} {e['file']} "
              f"(match: {e['match']!r}): suppressed nothing — remove it")

    verdict = "PASS" if res.ok else "FAIL"
    print(f"analysis: {verdict} "
          f"({', '.join(p.name for p in res.passes)}; {dt:.1f}s)")

    if args.json:
        payload = res.to_dict()
        payload["elapsed_s"] = round(dt, 2)
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
