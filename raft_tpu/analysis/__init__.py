"""raftlint — repo-specific static analysis for raft-tpu.

Four checker families over the defect classes this codebase has paid
for at runtime (see docs/ANALYSIS.md for the rule catalog):

- :mod:`raft_tpu.analysis.jit_purity` — ``JIT101..JIT104``: host
  impurity inside jit-traced code;
- :mod:`raft_tpu.analysis.locks` — ``LOCK201/LOCK202``: guarded-
  attribute discipline and lock-acquisition-order cycles;
- :mod:`raft_tpu.analysis.telemetry` — ``TEL301..TEL305``: emission
  sites vs the OBSERVABILITY.md catalog vs regression-gate keys;
- :mod:`raft_tpu.analysis.contracts` — ``CFG401/CFG402``: argparse
  flags vs the code that reads them vs the docs that name them.

Entry points: ``python -m raft_tpu lint`` (CLI) and
``scripts/lint_repo.py`` (bench-style JSON record + ``--fix``).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from raft_tpu.analysis.core import (  # noqa: F401  (public API)
    Finding, SourceFile, Workspace, load_baseline, load_report,
    make_report, split_findings, write_baseline,
)

BASELINE_PATH = "lint_baseline.json"

#: family name -> (module, rule IDs) — the registry ``run_checks``
#: dispatches on and ``--only`` filters by.
CHECKER_FAMILIES = {
    "jit": ("raft_tpu.analysis.jit_purity",
            ("JIT101", "JIT102", "JIT103", "JIT104")),
    "locks": ("raft_tpu.analysis.locks", ("LOCK201", "LOCK202")),
    "telemetry": ("raft_tpu.analysis.telemetry",
                  ("TEL301", "TEL302", "TEL303", "TEL304", "TEL305")),
    "contracts": ("raft_tpu.analysis.contracts",
                  ("CFG401", "CFG402")),
}


def run_checks(ws: Workspace,
               families: Optional[Sequence[str]] = None,
               ) -> Tuple[List[Finding], List[str]]:
    """Run the selected checker families (default: all) against the
    workspace.  Returns ``(findings, rules_run)`` — unfiltered;
    callers route through :func:`split_findings` for suppression and
    baseline handling."""
    import importlib

    findings: List[Finding] = []
    rules: List[str] = []
    for family in (families or sorted(CHECKER_FAMILIES)):
        if family not in CHECKER_FAMILIES:
            raise ValueError(
                f"unknown checker family {family!r}; have "
                f"{sorted(CHECKER_FAMILIES)}")
        modname, family_rules = CHECKER_FAMILIES[family]
        mod = importlib.import_module(modname)
        findings.extend(mod.check(ws))
        rules.extend(family_rules)
    return findings, rules


def files_scanned(ws: Workspace) -> int:
    return sum(1 for sf in ws._cache.values() if sf is not None)
