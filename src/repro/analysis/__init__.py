"""Static-analysis suite (DESIGN.md §12): four passes, one gate.

``run_suite`` executes the AST linter, the retrace auditor, the
sharding checker, and the ledger auditor, applies the checked-in
baseline, and reports a single ok/fail — the same entry the
``repro.launch.analyze`` CLI and the CI ``analysis`` job use.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from repro.analysis.common import (Baseline, Finding, apply_baseline,
                                   repo_root)

ALL_PASSES = ("lint", "retrace", "sharding", "ledger")


@dataclasses.dataclass
class PassResult:
    name: str
    fresh: List[Finding]
    suppressed: List[Finding]
    notes: List[str]

    @property
    def ok(self) -> bool:
        return not self.fresh


@dataclasses.dataclass
class SuiteResult:
    passes: List[PassResult]
    stale_baseline: List[dict]

    @property
    def ok(self) -> bool:
        return (all(p.ok for p in self.passes)
                and not self.stale_baseline)

    def to_dict(self) -> Dict:
        return {
            "ok": self.ok,
            "passes": {
                p.name: {
                    "ok": p.ok,
                    "fresh": [dataclasses.asdict(f) for f in p.fresh],
                    "suppressed": len(p.suppressed),
                    "notes": p.notes,
                } for p in self.passes
            },
            "stale_baseline": self.stale_baseline,
        }


def run_suite(passes: Sequence[str] = ALL_PASSES,
              arch_ids: Optional[Sequence[str]] = None,
              root: Optional[str] = None,
              baseline_path: Optional[str] = None) -> SuiteResult:
    """Run the requested passes against the repo at ``root``.

    Baseline staleness is only judged when every pass ran (a subset run
    cannot tell whether the other passes' entries still suppress)."""
    from repro.analysis import ledger, lint, retrace, sharding

    root = root or repo_root()
    bl = Baseline.load(baseline_path)
    results: List[PassResult] = []
    for name in passes:
        notes: List[str] = []
        if name == "lint":
            found = lint.run_lint(root)
        elif name == "retrace":
            found, reports = retrace.run_retrace(arch_ids)
            n_variants = sum(
                sum(len(v) for v in r.signatures.values())
                + len(r.errors) for r in reports)
            notes.append(f"{len(reports)} entrypoint audits, "
                         f"{n_variants} traced variants, "
                         f"{sum(1 for r in reports if r.ok)} single-"
                         f"signature")
        elif name == "sharding":
            found, summary = sharding.run_sharding(arch_ids)
            leaves = sum(s["leaves"] for s in summary.values())
            sharded = sum(s["sharded"] for s in summary.values())
            notes.append(f"{len(summary)} configs, {leaves} leaf×mesh "
                         f"specs checked, {sharded} sharded")
        elif name == "ledger":
            found, detail = ledger.run_ledger(root)
            notes.append(f"{len(detail['written'])} fields written, "
                         f"{len(detail['consumed'])} consumed by "
                         f"aggregate(), "
                         f"{len(detail['written']) - len(detail['consumed'] & detail['written'])}"
                         f" waived")
        else:
            raise ValueError(f"unknown analysis pass {name!r}")
        fresh, suppressed = apply_baseline(found, bl)
        results.append(PassResult(name=name, fresh=fresh,
                                  suppressed=suppressed, notes=notes))
    stale = bl.stale() if set(passes) >= set(ALL_PASSES) else []
    return SuiteResult(passes=results, stale_baseline=stale)


__all__ = ["ALL_PASSES", "Baseline", "Finding", "PassResult",
           "SuiteResult", "run_suite"]
