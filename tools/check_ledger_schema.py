#!/usr/bin/env python
"""Thin shim over distlint rule DL006 (the ledger-schema check's new home).

The original AST walker grew into ``tools/distlint`` — a whole-tree
SPMD-correctness linter — and this check became its rule DL006, so there
is exactly ONE AST walker to maintain. This entry point stays for
callers/CI muscle memory and keeps the original API surface:

* :func:`load_schema` — EVENT_SCHEMA extracted from ledger.py by AST;
* :func:`check_file` — one file's violations as ``rel:line: msg`` strings;
* :func:`check_tree` — the historical sweep (tpu_dist, tools, tests,
  scripts), same string format;
* CLI: ``python tools/check_ledger_schema.py [root]`` — prints violations,
  exits non-zero if any.

``# ledger-schema: forward`` on a call line still declares a forwarding
wrapper (distlint's DL006 honors it), and ``# distlint: disable=DL006 --
reason`` now works too.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # script invocation: make 'tools.distlint' importable
    sys.path.insert(0, ROOT)

from tools.distlint.core import (FileContext, load_event_schema,  # noqa: E402
                                 lint_files, parse_suppressions)
from tools.distlint.rules import check_emit_calls  # noqa: E402

SCHEMA_FILE = os.path.join("tpu_dist", "obs", "ledger.py")
CHECKED = ("tpu_dist", "tools", "tests", "scripts")
FORWARD_MARK = "ledger-schema: forward"


def load_schema(root: str = ROOT) -> dict:
    return load_event_schema(root)


def check_file(path: str, schema: dict, rel: str) -> list:
    """One file's DL006 violations in the historical string format.
    Honors the same suppressions as the lint gate (`# distlint:
    disable=DL006 -- reason`), so the two API surfaces always agree."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        ctx = FileContext(path, rel, src)
    except SyntaxError as e:
        return [f"{rel}: unparseable ({e})"]
    sups, _ = parse_suppressions(src)
    suppressed = {s.line for s in sups if "DL006" in s.rules}
    return [f"{f.path}:{f.line}: {f.message}"
            for f in check_emit_calls(ctx, schema)
            if f.line not in suppressed]


def check_tree(root: str = ROOT) -> list:
    """The historical sweep, now one distlint invocation (DL006 only;
    distlint's walker skips fixture dirs, where deliberately bad emit
    calls live as linter test data)."""
    paths = [d for d in CHECKED if os.path.isdir(os.path.join(root, d))]
    result = lint_files(paths, root=root, select=["DL006"])
    return [f"{f.path}:{f.line}: {f.message}" for f in result.findings]


def main(argv=None) -> int:
    root = (argv or sys.argv[1:] or [ROOT])[0]
    violations = check_tree(root)
    for v in violations:
        print(v, file=sys.stderr)
    print(f"check_ledger_schema: {len(violations)} violation(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
