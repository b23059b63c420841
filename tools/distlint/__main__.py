"""CLI: ``python -m tools.distlint [paths...]``.

Exit code 1 when any unsuppressed ERROR-tier finding exists (warn-tier
findings print but never gate — scripts/lint.sh relies on this), 2 on
usage errors, 0 otherwise. The default path set is the full acceptance
surface — tpu_dist, tools (the linter lints itself), tests, scripts —
and the tree stays pinned at zero findings.

Formats: ``--format human|json|sarif`` (``--json`` is a legacy alias);
``--sarif-out FILE`` additionally writes the SARIF artifact beside any
format, which is how CI gets a code-scanning upload from the same run.
``--debt`` prints the suppression inventory (per-rule counts, reasons,
file age, staleness) instead of gating — advisory by design.
"""

from __future__ import annotations

import argparse
import json
import sys

from tools.distlint.core import REPO_ROOT, lint_files
from tools.distlint.report import (collect_debt, render_debt,
                                   split_by_severity, to_sarif)
from tools.distlint.rules import RULES

DEFAULT_PATHS = ["tpu_dist", "tools", "tests", "scripts"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.distlint",
        description="AST-based SPMD-correctness and concurrency-safety "
                    "linter (stdlib-only).")
    ap.add_argument("paths", nargs="*", default=None,
                    help=f"files/dirs to lint (default: {DEFAULT_PATHS})")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root (mesh axes / ledger schema / call "
                         "graph are loaded relative to it)")
    ap.add_argument("--select", default=None, metavar="DL001,DL002",
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--format", default=None, dest="fmt",
                    choices=("human", "json", "sarif"),
                    help="output format (default: human)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="legacy alias for --format json")
    ap.add_argument("--sarif-out", default=None, metavar="FILE",
                    help="also write a SARIF 2.1.0 artifact to FILE")
    ap.add_argument("--debt", action="store_true",
                    help="print the suppression-debt inventory (advisory: "
                         "always exits 0)")
    ap.add_argument("--with-debt", action="store_true",
                    help="append the debt inventory after the findings "
                         "summary of the SAME run (what scripts/lint.sh "
                         "uses — no second full lint)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in RULES:
            sev = getattr(r, "severity", "error")
            print(f"{r.id}  [{sev}]  {r.title}\n       {r.rationale}")
        return 0

    fmt = args.fmt or ("json" if args.as_json else "human")
    select = ([s.strip() for s in args.select.split(",") if s.strip()]
              if args.select else None)
    if select:
        known = {r.id for r in RULES}
        bad = sorted(set(select) - known)
        if bad:
            print(f"distlint: unknown rule id(s) {bad} "
                  f"(known: {sorted(known)})", file=sys.stderr)
            return 2
    paths = args.paths or DEFAULT_PATHS
    try:
        result = lint_files(paths, root=args.root, select=select)
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 2

    if args.sarif_out:   # before --debt: the artifact writes either way
        with open(args.sarif_out, "w") as f:
            json.dump(to_sarif(result), f, indent=2, sort_keys=True)

    # staleness is only decidable against a FULL-rule result: under
    # --select, pins for unselected rules match no finding by
    # construction and would all be mislabeled deletable debt
    debt_result = result if select is None else None

    if args.debt:
        debt = collect_debt(paths, args.root, debt_result)
        if fmt == "json":
            print(json.dumps(debt, indent=2, sort_keys=True))
        else:
            print(render_debt(debt))
        return 0

    errors, warns = split_by_severity(result)
    if fmt == "sarif":
        print(json.dumps(to_sarif(result), indent=2, sort_keys=True))
    elif fmt == "json":
        payload = result.to_json()
        payload["errors"] = len(errors)
        payload["warnings"] = len(warns)
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        for f in result.findings:
            print(f.render())
        print(f"distlint: {len(errors)} error(s), {len(warns)} "
              f"warning(s), {len(result.suppressed)} suppressed, "
              f"{result.files_checked} file(s) checked")
    if args.with_debt:
        # advisory inventory from THIS run's result — no second sweep;
        # goes to stderr under json/sarif so stdout stays parseable
        print(render_debt(collect_debt(paths, args.root, debt_result)),
              file=sys.stderr if fmt != "human" else sys.stdout)
    return 1 if errors else 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except BrokenPipeError:   # `... | head` closed the pipe: not an error
        raise SystemExit(0)
