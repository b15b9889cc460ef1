"""Static-contracts gate: AST lint, lowered-IR collective budgets, shape audit.

    python scripts/check_static.py                    # full gate, exit 1 on any violation
    python scripts/check_static.py --lint-target F..  # lint specific files (exit 1 on findings)
    python scripts/check_static.py --contract-fixture extra_psum
    python scripts/check_static.py --shape-fixture

Three layers (DESIGN.md §12, ``repro.staticcheck``):

  1. repo AST lint    RS001-RS005 strict over src/repro + scripts,
                      warn-only over tests/
  2. IR contracts     lower all five engine backends + the sharded ring
                      write under a forced multi-device mesh, assert the
                      declared collective set / byte budget / reduce axis
  3. shape audit      >= 5 steady-state streaming slides and a cache-warm
                      mine run under jax.transfer_guard + the compile log:
                      zero recompiles, zero implicit transfers, every
                      recorded padding on the bucket ladder

The gate also self-tests its teeth: every committed must-fail fixture
(rs00*_bad.py, the four IR contract fixtures, the shape fixture) must still
produce findings — a fixture that passes means the checker rotted, and the
gate fails the build for it.

Writes a machine-readable findings report (default
``reports/static_findings.json``) for the CI artifact.  Run by CI next to
``scripts/check_docs.py`` and by tests/test_staticcheck.py.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "src"))

# layers 2/3 need a multi-device mesh; append, never overwrite (RS004) —
# must happen before anything imports jax
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

from repro.staticcheck import (Report, iter_python_files, lint_paths,  # noqa: E402
                               rule_ids)
from repro.staticcheck.astlint import lint_file  # noqa: E402

FIXTURE_DIR = os.path.join(ROOT, "src", "repro", "staticcheck", "fixtures")
STRICT_DIRS = (os.path.join("src", "repro"), "scripts")
WARN_DIRS = ("tests",)
DEFAULT_REPORT = os.path.join(ROOT, "reports", "static_findings.json")


def _print(findings, label: str) -> None:
    for f in findings:
        print(f"static: [{label}] {f.format()}", file=sys.stderr)


# ---------------------------------------------------------------------------
# layer 1: AST lint
# ---------------------------------------------------------------------------

def run_lint(report: Report) -> int:
    strict = lint_paths(iter_python_files(ROOT, STRICT_DIRS), root=ROOT)
    warn = lint_paths(iter_python_files(ROOT, WARN_DIRS), root=ROOT,
                      severity="warning")
    _print(strict, "lint")
    _print(warn, "lint/warn-only")
    report.extend(strict)
    report.extend(warn)
    print(f"static: lint strict={len(strict)} warn-only={len(warn)}")
    return len(strict)


def run_lint_fixtures(report: Report) -> int:
    """Every rule's must-fail fixture must still trip exactly that rule."""
    failures = 0
    for rid in rule_ids():
        path = os.path.join(FIXTURE_DIR, f"{rid.lower()}_bad.py")
        found = lint_file(path, root=ROOT)
        if not any(f.rule == rid for f in found):
            failures += 1
            print(f"static: FIXTURE ROTTED — {os.path.relpath(path, ROOT)} "
                  f"no longer triggers {rid}", file=sys.stderr)
    report.summary["lint_fixtures"] = {
        "checked": len(rule_ids()), "rotted": failures}
    print(f"static: lint fixtures {len(rule_ids()) - failures}/"
          f"{len(rule_ids())} still fail as committed")
    return failures


# ---------------------------------------------------------------------------
# layer 2: lowered-IR contracts
# ---------------------------------------------------------------------------

def run_contracts(report: Report) -> int:
    from repro.staticcheck.contracts import check_all_contracts

    findings, summary = check_all_contracts()
    _print(findings, "ir")
    report.extend(findings)
    report.summary["ir_contracts"] = summary
    n_targets = len(summary["backends"]) + 1          # + the ring write
    print(f"static: IR contracts over {n_targets} lowered targets, "
          f"{len(findings)} finding(s)")
    return len(findings)


def run_contract_fixtures(report: Report) -> int:
    from repro.staticcheck.contracts import (CONTRACT_FIXTURES,
                                             check_contract_fixture)

    failures = 0
    for name in sorted(CONTRACT_FIXTURES):
        found = check_contract_fixture(name)
        if not found:
            failures += 1
            print(f"static: FIXTURE ROTTED — IR fixture {name!r} no longer "
                  f"violates its contract", file=sys.stderr)
    report.summary["ir_fixtures"] = {
        "checked": len(CONTRACT_FIXTURES), "rotted": failures}
    print(f"static: IR fixtures {len(CONTRACT_FIXTURES) - failures}/"
          f"{len(CONTRACT_FIXTURES)} still fail as committed")
    return failures


# ---------------------------------------------------------------------------
# layer 3: runtime-shape audit
# ---------------------------------------------------------------------------

def run_shapes(report: Report) -> int:
    import jax

    from repro.dist.compat import make_mesh
    from repro.staticcheck.shapes import audit_mine, audit_streaming

    n_findings = 0
    summaries = []
    targets = [("pallas", "pairs", None)]
    if len(jax.devices()) >= 2:
        n = 4 if len(jax.devices()) >= 4 else 2
        targets.append(("tidsharded", "words",
                        make_mesh((n,), ("data",),
                                  devices=jax.devices()[:n])))
    for backend, shard, mesh in targets:
        findings, summary = audit_streaming(backend=backend, shard=shard,
                                            mesh=mesh)
        _print(findings, "shape")
        report.extend(findings)
        summaries.append(summary)
        n_findings += len(findings)
        print(f"static: shape audit {summary['target']} — "
              f"{summary['audited_slides']} audited slides, "
              f"{len(findings)} finding(s)")
    findings, summary = audit_mine()
    _print(findings, "shape")
    report.extend(findings)
    summaries.append(summary)
    n_findings += len(findings)
    print(f"static: shape audit {summary['target']} — "
          f"{summary['levels']} levels, {len(findings)} finding(s)")
    report.summary["shape_audits"] = summaries
    return n_findings


def run_shape_fixture(report: Report) -> int:
    from repro.staticcheck.shapes import check_shape_fixture

    found = check_shape_fixture()
    rules = sorted({f.rule for f in found})
    rotted = 0 if {"SH001", "SH002", "SH003"} <= set(rules) else 1
    if rotted:
        print(f"static: FIXTURE ROTTED — shape fixture only triggered "
              f"{rules}, expected SH001+SH002+SH003", file=sys.stderr)
    report.summary["shape_fixture"] = {"rules": rules, "rotted": rotted}
    print(f"static: shape fixture trips {rules}")
    return rotted


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def full_gate(report_path: str) -> int:
    report = Report()
    bad = 0
    bad += run_lint(report)
    bad += run_lint_fixtures(report)
    bad += run_contracts(report)
    bad += run_contract_fixtures(report)
    bad += run_shapes(report)
    bad += run_shape_fixture(report)
    report.summary["violations"] = bad
    report.write(report_path)
    print(f"static: report -> {os.path.relpath(report_path, ROOT)}")
    if bad:
        print(f"static: {bad} violation(s)", file=sys.stderr)
        return 1
    print("static: OK")
    return 0


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--lint-target", nargs="+", metavar="PATH",
                    help="lint specific files/dirs; exit 1 on any finding")
    ap.add_argument("--contract-fixture", metavar="NAME",
                    help="run one IR contract fixture; exit 1 when it "
                         "violates its contract (the committed ones must)")
    ap.add_argument("--shape-fixture", action="store_true",
                    help="run the shape-audit fixture; exit 1 when it "
                         "produces findings (the committed one must)")
    ap.add_argument("--report", default=DEFAULT_REPORT, metavar="PATH",
                    help="findings report path (default "
                         "reports/static_findings.json)")
    args = ap.parse_args(argv)

    if args.lint_target:
        findings = []
        for target in args.lint_target:
            path = os.path.abspath(target)
            if os.path.isdir(path):
                findings.extend(lint_paths(
                    iter_python_files(ROOT, [os.path.relpath(path, ROOT)]),
                    root=ROOT))
            else:
                findings.extend(lint_file(path, root=ROOT))
        _print(findings, "lint")
        print(f"static: {len(findings)} finding(s)")
        return 1 if findings else 0

    if args.contract_fixture:
        from repro.staticcheck.contracts import check_contract_fixture

        findings = check_contract_fixture(args.contract_fixture)
        _print(findings, "ir")
        print(f"static: {len(findings)} finding(s)")
        return 1 if findings else 0

    if args.shape_fixture:
        from repro.staticcheck.shapes import check_shape_fixture

        findings = check_shape_fixture()
        _print(findings, "shape")
        print(f"static: {len(findings)} finding(s)")
        return 1 if findings else 0

    return full_gate(os.path.abspath(args.report))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
