"""graftlint runner — checker registry, project lint entry points, CLI.

`lint_project(root)` is the programmatic surface tests use;
`main(argv)` is `python -m tools.graftlint sptag_tpu/`.
Exit codes: 0 = clean (all findings baseline-suppressed), 1 = new
unsuppressed findings, 2 = usage / baseline-format error.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from tools.graftlint import (asyncrules, attrmodel, concurrency,
                             dtype_parity, errorpath, guardedby, hostsync,
                             lockgraph, obsgraph, obsnames, persistrules,
                             retrace, tracecontract)
from tools.graftlint.baseline import (BaselineError, Suppression,
                                      apply_baseline, load_baseline)
from tools.graftlint.core import Finding, Project

CHECKERS = (hostsync, retrace, concurrency, errorpath, dtype_parity,
            obsnames, lockgraph, asyncrules, persistrules,
            guardedby, tracecontract, attrmodel, obsgraph)

#: rule id -> one-line description, collected from every checker module
ALL_RULES: Dict[str, str] = {}
for _mod in CHECKERS:
    ALL_RULES.update(_mod.RULES)

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.toml")


def run_checkers(project: Project,
                 select: Optional[Sequence[str]] = None) -> List[Finding]:
    """All findings (plus parse errors), sorted by location.  `select`
    restricts to rule-id prefixes (e.g. ["GL3"] or ["GL301"])."""
    findings: List[Finding] = list(project.errors)
    for checker in CHECKERS:
        if select and not any(rule.startswith(s)
                              for rule in checker.RULES
                              for s in select):
            continue          # no selected rule — skip the whole pass
        findings.extend(checker.check(project))
    if select:
        findings = [f for f in findings
                    if any(f.rule.startswith(s) for s in select)]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def lint_project(root: str, baseline_path: Optional[str] = None,
                 select: Optional[Sequence[str]] = None
                 ) -> Tuple[List[Finding], List[Finding],
                            List[Suppression]]:
    """-> (unsuppressed, suppressed, stale_suppressions)."""
    project = Project.from_tree(root)
    findings = run_checkers(project, select=select)
    if baseline_path is None:
        return findings, [], []
    suppressions = load_baseline(baseline_path)
    unsuppressed, suppressed = apply_baseline(findings, suppressions)
    stale = [s for s in suppressions if s.hits == 0]
    return unsuppressed, suppressed, stale


def lint_sources(sources: Dict[str, str],
                 select: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint in-memory sources (the unit-test surface): {relpath: text}."""
    return run_checkers(Project(sources), select=select)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint",
        description="TPU/JAX static-analysis suite for sptag_tpu "
                    "(host-sync, retrace, concurrency, error-path, "
                    "dtype-parity, observability-names, lock-order/"
                    "blocking-under-lock, sync-async hazards)")
    parser.add_argument("paths", nargs="*", default=["sptag_tpu"],
                        help="package roots to lint (default: sptag_tpu)")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE,
                        help="accepted-findings file (default: "
                             "tools/graftlint/baseline.toml)")
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding, suppressing nothing")
    parser.add_argument("--select", action="append", default=None,
                        metavar="RULE",
                        help="only run rules with this id prefix "
                             "(repeatable, e.g. --select GL1)")
    parser.add_argument("--list-rules", action="store_true")
    parser.add_argument("--schema-dump", action="store_true",
                        help="boot a server+aggregator in-process with "
                             "all telemetry armed, scrape every surface, "
                             "and diff the live exposition against the "
                             "static ObsModel (both directions)")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in sorted(ALL_RULES):
            print(f"{rule}  {ALL_RULES[rule]}")
        return 0

    if args.schema_dump:
        from tools.graftlint import schemadump
        return schemadump.main(args.paths or ["sptag_tpu"])

    baseline_path = None if args.no_baseline else args.baseline
    if baseline_path is not None and not os.path.exists(baseline_path):
        if baseline_path != DEFAULT_BASELINE:
            # an EXPLICIT --baseline that does not exist is a usage
            # error — silently linting baseline-less would misreport
            # every accepted finding as a new regression
            print(f"graftlint: baseline file not found: {baseline_path}",
                  file=sys.stderr)
            return 2
        baseline_path = None

    # lint every root first, THEN apply the baseline once over the
    # combined findings — per-root application would double-load the
    # suppressions and misreport entries satisfied by another root as
    # stale
    t0 = time.monotonic()
    findings: List[Finding] = []
    for root in (args.paths or ["sptag_tpu"]):
        if not os.path.isdir(root):
            print(f"graftlint: no such directory: {root}", file=sys.stderr)
            return 2
        findings.extend(run_checkers(Project.from_tree(root),
                                     select=args.select))
    stale: List[Suppression] = []
    total_suppressed = 0
    total_unsuppressed = findings
    if baseline_path is not None:
        try:
            suppressions = load_baseline(baseline_path)
        except BaselineError as e:
            print(f"graftlint: {e}", file=sys.stderr)
            return 2
        total_unsuppressed, suppressed = apply_baseline(findings,
                                                        suppressions)
        total_suppressed = len(suppressed)
        # under --select, only suppressions for the selected rules can
        # meaningfully be stale — the others never had a chance to match
        stale = [s for s in suppressions if s.hits == 0
                 and (not args.select
                      or any(s.rule.startswith(p) for p in args.select))]

    for f in total_unsuppressed:
        print(f.format())
    for s in stale:
        print(f"graftlint: note: stale baseline entry "
              f"({s.rule} {s.path} {s.symbol or '*'}) matched nothing — "
              "prune it", file=sys.stderr)
    n = len(total_unsuppressed)
    elapsed = time.monotonic() - t0
    print(f"graftlint: {n} finding(s), {total_suppressed} "
          f"baseline-suppressed, {len(stale)} stale baseline entr"
          f"{'y' if len(stale) == 1 else 'ies'} in {elapsed:.2f}s",
          file=sys.stderr)
    return 1 if total_unsuppressed else 0
