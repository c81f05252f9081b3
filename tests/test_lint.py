"""raftlint: checker families, suppression, baseline, report, gates.

Fixture layout: tests/lint_fixtures/README.md.  Every rule family is
tested both ways — the violation fixture must fire (with the right
rule ID and line), and the clean twin must stay silent (a checker that
stopped looking would pass the twin trivially but fail the violation
side).  The final test runs the real checkers over the real repo: the
tree itself must lint clean modulo the committed baseline.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

from raft_tpu.analysis import (BASELINE_PATH, Workspace, contracts,
                               files_scanned, jit_purity, load_baseline,
                               load_report, locks, make_report,
                               run_checks, split_findings, telemetry,
                               write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")


def fixture_ws(name):
    return Workspace(os.path.join(FIXTURES, name))


def by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


# ---------------------------------------------------------------------
# jit-purity family
# ---------------------------------------------------------------------


def test_jit_violations_fire_with_rule_ids_and_lines():
    rules = by_rule(jit_purity.check(fixture_ws("jit_violation")))
    # host calls: the decorated root AND the jax.jit(_inner) call-site
    # root both reach the purity pass
    lines = {f.line for f in rules["JIT101"]}
    assert {12, 13, 22} <= lines
    assert {f.line for f in rules["JIT102"]} == {14, 15}
    assert [f.line for f in rules["JIT104"]] == [16]
    [blk] = rules["JIT103"]
    assert (blk.path, blk.line) == ("raft_tpu/ops/sync.py", 5)


def test_jit_clean_twin_is_silent():
    assert jit_purity.check(fixture_ws("jit_clean")) == []


# ---------------------------------------------------------------------
# lock-discipline family
# ---------------------------------------------------------------------


def test_lock_violations_fire_self_and_cross_object():
    rules = by_rule(locks.check(fixture_ws("locks_violation")))
    lines = {f.line for f in rules["LOCK201"]}
    assert lines == {17, 35}  # self-form in reset(), cross in poke()
    assert all(f.detail == "Engine._pending"
               for f in rules["LOCK201"])
    [cyc] = rules["LOCK202"]
    assert set(cyc.detail.split("->")) == {"Engine._lock",
                                           "Engine._aux"}


def test_lock_clean_twin_is_silent():
    assert locks.check(fixture_ws("locks_clean")) == []


# ---------------------------------------------------------------------
# telemetry-contract family
# ---------------------------------------------------------------------


def test_telemetry_violations_fire_all_five_rules():
    rules = by_rule(telemetry.check(fixture_ws("telemetry_violation")))
    assert set(rules) == {"TEL301", "TEL302", "TEL303", "TEL304",
                          "TEL305"}
    assert rules["TEL301"][0].detail == "raft_undocumented_total"
    assert rules["TEL302"][0].detail == "raft_stale_metric_total"
    assert rules["TEL303"][0].detail == "undocumented_event"
    assert rules["TEL304"][0].detail == "stale_event"
    assert rules["TEL305"][0].detail == "ghost_key"


def test_telemetry_clean_twin_is_silent():
    assert telemetry.check(fixture_ws("telemetry_clean")) == []


def test_telemetry_fix_appends_placeholder_rows():
    ws = fixture_ws("telemetry_violation")
    findings = [f for f in telemetry.check(ws)
                if f.rule in ("TEL301", "TEL303")]
    new_text, n = telemetry.fix_documentation(ws, findings)
    assert n == 2
    assert "raft_undocumented_total" in new_text
    assert "undocumented_event" in new_text
    # the appended rows land INSIDE the right tables: re-parsing the
    # fixed doc resolves both TEL301/TEL303 findings
    cat = telemetry.DocCatalog(new_text)
    assert "raft_undocumented_total" in cat.metric_rows
    assert "undocumented_event" in cat.event_rows


# ---------------------------------------------------------------------
# config/CLI contract family
# ---------------------------------------------------------------------


def test_contract_violations_fire_both_rules():
    rules = by_rule(contracts.check(fixture_ws("contracts_violation")))
    assert set(rules) == {"CFG401", "CFG402"}
    [dead] = rules["CFG401"]
    assert (dead.path, dead.line) == ("raft_tpu/cli/train.py", 9)
    assert "--dead-flag" in dead.detail
    [phantom] = rules["CFG402"]
    assert phantom.detail == "--phantom-flag"


def test_contract_clean_twin_is_silent():
    assert contracts.check(fixture_ws("contracts_clean")) == []


# ---------------------------------------------------------------------
# suppression + baseline + report round-trips
# ---------------------------------------------------------------------


def test_inline_pragma_suppresses_and_skip_file_opts_out():
    ws = fixture_ws("suppressed")
    findings = jit_purity.check(ws)
    # skipped.py contributed nothing (skip-file); net.py's finding is
    # pragma-suppressed
    assert [f.path for f in findings] == ["raft_tpu/models/net.py"]
    active, baselined, suppressed = split_findings(ws, findings, {})
    assert active == [] and baselined == []
    assert [f.rule for f in suppressed] == ["JIT101"]


def test_baseline_round_trip(tmp_path):
    ws = fixture_ws("jit_violation")
    findings = jit_purity.check(ws)
    assert findings
    path = str(tmp_path / "baseline.json")
    write_baseline(findings, path,
                   default_justification="fixture debt")
    baseline = load_baseline(path)
    assert set(baseline) == {f.key for f in findings}
    assert all(j == "fixture debt" for j in baseline.values())
    active, baselined, suppressed = split_findings(ws, findings,
                                                   baseline)
    assert active == [] and suppressed == []
    assert len(baselined) == len(findings)
    # keys are line-number-free: an unrelated edit shifting lines must
    # not resurrect baselined findings
    assert not any(":%d" % f.line == f.key.rsplit(":", 1)[-1]
                   for f in findings)


def test_baseline_requires_justification(tmp_path):
    ws = fixture_ws("jit_violation")
    findings = jit_purity.check(ws)
    with pytest.raises(ValueError):
        write_baseline(findings, str(tmp_path / "b.json"))


def test_report_round_trip(tmp_path):
    ws = fixture_ws("jit_violation")
    findings = jit_purity.check(ws)
    active, baselined, suppressed = split_findings(ws, findings, {})
    report = make_report(active, baselined, suppressed,
                         files_scanned(ws), ["JIT101"])
    path = str(tmp_path / "report.json")
    with open(path, "w") as f:
        json.dump(report, f)
    loaded, err = load_report(path)
    assert err is None
    assert loaded["total"] == len(active) > 0
    assert loaded["counts_by_rule"]["JIT101"] >= 1


def test_report_rejects_garbage(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("not json {")
    loaded, err = load_report(str(p))
    assert loaded is None and "not JSON" in err
    p.write_text(json.dumps({"tool": "flake8", "findings": []}))
    loaded, err = load_report(str(p))
    assert loaded is None and "raftlint" in err
    loaded, err = load_report(str(tmp_path / "missing.json"))
    assert loaded is None and "cannot read" in err


# ---------------------------------------------------------------------
# regression-gate integration (check_regression.py --lint-report)
# ---------------------------------------------------------------------


def _load_gate():
    spec = importlib.util.spec_from_file_location(
        "check_regression",
        os.path.join(REPO, "scripts", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_gate_passes_clean_fails_findings_and_missing(tmp_path):
    gate = _load_gate()
    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(
        {"tool": "raftlint", "findings": [], "total": 0}))
    assert gate.lint_gate(str(clean)) == []
    dirty = tmp_path / "dirty.json"
    dirty.write_text(json.dumps({
        "tool": "raftlint", "total": 1,
        "counts_by_rule": {"JIT101": 1},
        "findings": [{"rule": "JIT101", "path": "x.py", "line": 3,
                      "detail": "time.time", "message": "host call"}]}))
    [msg] = gate.lint_gate(str(dirty))
    assert "JIT101" in msg and "1 non-baselined" in msg
    [msg] = gate.lint_gate(str(tmp_path / "never_written.json"))
    assert "refusing to pass" in msg


def test_gate_selftest_includes_lint_cases():
    gate = _load_gate()
    assert gate._selftest() == 0


# ---------------------------------------------------------------------
# CLI + the repo gates itself
# ---------------------------------------------------------------------


def test_lint_cli_exit_codes(tmp_path, capsys):
    from raft_tpu.cli import lint as lint_cli

    rc = lint_cli.main(["--root",
                        os.path.join(FIXTURES, "jit_violation"),
                        "--no-baseline", "--only", "jit"])
    assert rc == 1
    captured = capsys.readouterr()
    assert "JIT101" in captured.out
    # the summary goes to stderr when findings are active (CI logs
    # surface it next to the nonzero exit)
    assert "finding(s)" in captured.err
    rc = lint_cli.main(["--root", os.path.join(FIXTURES, "jit_clean"),
                        "--no-baseline", "--only", "jit"])
    assert rc == 0
    rc = lint_cli.main(["--only", "bogus-family"])
    assert rc == 2


def test_lint_cli_writes_gateable_json(tmp_path):
    from raft_tpu.cli import lint as lint_cli

    out = str(tmp_path / "report.json")
    rc = lint_cli.main(["--root",
                        os.path.join(FIXTURES, "contracts_violation"),
                        "--no-baseline", "--only", "contracts",
                        "--json", out])
    assert rc == 1
    loaded, err = load_report(out)
    assert err is None
    assert loaded["total"] == 2
    assert set(loaded["counts_by_rule"]) == {"CFG401", "CFG402"}


def test_whole_repo_lints_clean_modulo_baseline():
    """Tier-1 enforcement: the tree must satisfy its own lint suite.
    A new finding either gets fixed or a justified baseline entry —
    this test is what makes that a merge gate."""
    ws = Workspace(REPO)
    findings, rules_run = run_checks(ws, None)
    baseline = load_baseline(os.path.join(REPO, BASELINE_PATH))
    active, _baselined, _suppressed = split_findings(ws, findings,
                                                     baseline)
    assert active == [], (
        "repo has non-baselined lint findings:\n" + "\n".join(
            f"  {f.rule} {f.path}:{f.line}: {f.message}"
            for f in active))
    # the run was not vacuous: all four families executed and the
    # scoped file sets parsed
    assert {"JIT101", "LOCK201", "TEL301", "CFG401"} <= set(rules_run)
    assert files_scanned(ws) > 50
