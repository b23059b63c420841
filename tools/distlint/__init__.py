"""distlint — AST-based SPMD-correctness + concurrency linter for tpu_dist.

Stdlib-only (ast + tokenize, no jax import): statically catches the
distributed failure classes the runtime watchdog can only report after
they hang a pod — collectives under host-divergent guards, blocking host
syncs on the hot step path, typo'd mesh axis names, untraced side effects
inside jitted code, PRNG key reuse, ledger schema drift, donated-buffer
reuse — plus, on the cross-file call graph + reachability engine
(:class:`~tools.distlint.core.CallGraph`), the DL1xx concurrency/signal-
safety family: plain-Lock-on-signal-path self-deadlocks (the PR-5 Ledger
SIGTERM class), blocking I/O under emit locks, non-daemon threads nobody
joins, and unsafe signal-handler bodies.

CLI::

    python -m tools.distlint                  # full surface, error-tier gate
    python -m tools.distlint --format sarif   # SARIF 2.1.0
    python -m tools.distlint --debt           # suppression inventory
    python -m tools.distlint --json --select DL002,DL101 tpu_dist

API::

    from tools.distlint import lint_files
    result = lint_files(["tpu_dist", "tools", "tests", "scripts"])
    assert result.findings == []

Suppressions are inline, with a REQUIRED reason::

    rows = np.asarray(x)  # distlint: disable=DL002 -- host array, not device

See tools/distlint/rules.py for the rule catalog (with severity tiers),
tools/distlint/report.py for SARIF/debt, and README.md ("Static
analysis") for the rule table.
"""

from tools.distlint.core import (CallGraph, Finding, LintResult, Project,
                                 REPO_ROOT, graph_scope, lint_files,
                                 load_callgraph, load_event_schema,
                                 load_mesh_axes, parse_suppressions)
from tools.distlint.report import (collect_debt, render_debt, severity_of,
                                   split_by_severity, to_sarif)
from tools.distlint.rules import RULES, RULES_BY_ID

__all__ = ["CallGraph", "Finding", "LintResult", "Project", "REPO_ROOT",
           "RULES", "RULES_BY_ID", "collect_debt", "graph_scope",
           "lint_files", "load_callgraph", "load_event_schema",
           "load_mesh_axes", "parse_suppressions", "render_debt",
           "severity_of", "split_by_severity", "to_sarif"]
