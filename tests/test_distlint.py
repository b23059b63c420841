"""distlint: rule fixtures, suppressions, JSON output, and the tier-1
clean-tree pin.

Every rule must flag its bad fixture (tests/fixtures/distlint/dlNNN_bad.py)
and stay silent on the good twin — a rule that cannot fire is worse than no
rule, because it pins a false "clean". The fixtures directory is excluded
from directory walks (distlint SKIP_DIRS), so the clean-tree sweep below
never sees the deliberate violations; fixtures are linted by explicit file
path only.

No jax import anywhere in this file: distlint is stdlib-only by contract,
and this suite must stay cheap inside the tier-1 budget.
"""

import json
import os
import subprocess
import sys

import pytest

from tools.distlint import RULES, lint_files, load_mesh_axes
from tools.distlint.core import (REPO_ROOT, load_callgraph,
                                 parse_suppressions)
from tools.distlint.report import (collect_debt, severity_of, to_sarif)
from tools.distlint.__main__ import main as distlint_main

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "distlint")
RULE_IDS = [r.id for r in RULES]

SURFACE = ["tpu_dist", "tools", "tests", "scripts"]
_FULL: list = []   # memoized full-surface lint (the most expensive call
#                    here — the pin test and the debt test share one run)


def _full_lint():
    if not _FULL:
        _FULL.append(lint_files(SURFACE))
    return _FULL[0]

# every rule must produce EXACTLY this many findings on its bad fixture —
# an extra finding is a false positive creeping into the rule, a missing
# one is a detection regression; both should fail loudly here
EXPECTED_BAD_COUNTS = {"DL001": 2, "DL002": 3, "DL003": 3,
                       "DL004": 4, "DL005": 3, "DL006": 19, "DL007": 2,
                       "DL008": 2,
                       "DL101": 1, "DL102": 2, "DL103": 2, "DL104": 3,
                       "DL201": 4}


def lint_fixture(name: str, rule_id: str):
    return lint_files([os.path.join(FIXTURES, name)], select=[rule_id])


# ------------------------------------------------------------ rule pairs
@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_fires_on_bad_fixture(rule_id):
    res = lint_fixture(f"dl{rule_id[2:]}_bad.py", rule_id)
    assert len(res.findings) == EXPECTED_BAD_COUNTS[rule_id], \
        [f.render() for f in res.findings]
    for f in res.findings:
        assert f.rule == rule_id
        assert f.line > 0 and f.message
        assert f.path.endswith(f"dl{rule_id[2:]}_bad.py")


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_silent_on_good_fixture(rule_id):
    res = lint_fixture(f"dl{rule_id[2:]}_good.py", rule_id)
    assert res.findings == [], [f.render() for f in res.findings]


def test_rules_have_distinct_ids_and_docs():
    assert len(RULE_IDS) == len(set(RULE_IDS)) >= 13
    for r in RULES:
        assert r.title and r.rationale
        assert getattr(r, "severity", None) in ("error", "warn")


# ----------------------------------------------------------- suppression
def _write(tmp_path, text):
    p = tmp_path / "snippet.py"
    p.write_text(text)
    return str(p)


BAD_LOOP = ("import jax\n"
            "def train_epoch(it, step, state):\n"
            "    for b in it:\n"
            "        state, m = step(state, b)\n"
            "        jax.device_get(m){}\n"
            "    return state\n")


def test_trailing_suppression_with_reason(tmp_path):
    path = _write(tmp_path, BAD_LOOP.format(
        "  # distlint: disable=DL002 -- test: deliberate sync"))
    res = lint_files([path], select=["DL002"])
    assert res.findings == []
    ((finding, sup),) = res.suppressed
    assert finding.rule == "DL002" and sup.reason == "test: deliberate sync"


def test_standalone_suppression_applies_to_next_line(tmp_path):
    lines = BAD_LOOP.format("").splitlines()
    lines.insert(4, "        # distlint: disable=DL002 -- test: deliberate "
                    "sync on next line")
    path = _write(tmp_path, "\n".join(lines) + "\n")
    res = lint_files([path], select=["DL002"])
    assert res.findings == []
    assert len(res.suppressed) == 1


def test_suppression_without_reason_is_itself_a_finding(tmp_path):
    path = _write(tmp_path, BAD_LOOP.format(
        "  # distlint: disable=DL002"))
    res = lint_files([path], select=["DL002"])
    rules = sorted(f.rule for f in res.findings)
    # the reasonless disable does NOT suppress, and is flagged as DL000
    assert rules == ["DL000", "DL002"], [f.render() for f in res.findings]
    assert "reason" in next(f for f in res.findings
                            if f.rule == "DL000").message


def test_suppression_for_other_rule_does_not_apply(tmp_path):
    path = _write(tmp_path, BAD_LOOP.format(
        "  # distlint: disable=DL001 -- wrong rule id"))
    res = lint_files([path], select=["DL002"])
    assert [f.rule for f in res.findings] == ["DL002"]
    assert res.suppressed == []


def test_multi_rule_suppression_parses():
    sups, malformed = parse_suppressions(
        "x = 1  # distlint: disable=DL001,DL005 -- both rules, one reason\n")
    assert malformed == []
    assert sups[0].rules == ("DL001", "DL005")
    assert sups[0].line == 1


def test_prose_mentioning_distlint_is_not_a_directive():
    sups, malformed = parse_suppressions(
        "# this comment mentions distlint casually, not as a directive\n"
        "x = 1\n")
    assert sups == [] and malformed == []


def test_unparseable_file_is_reported_not_crashed(tmp_path):
    path = _write(tmp_path, "def broken(:\n")
    res = lint_files([path])
    assert [f.rule for f in res.findings] == ["DL000"]
    assert "unparseable" in res.findings[0].message


# ------------------------------------------------------------ CLI + JSON
def test_cli_json_round_trip(tmp_path, capsys):
    bad = os.path.join(FIXTURES, "dl003_bad.py")
    rc = distlint_main(["--json", "--select", "DL003", bad])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    api = lint_files([bad], select=["DL003"])
    assert payload["findings"] == [f.to_json() for f in api.findings]
    assert payload["files_checked"] == 1
    for f in payload["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message"}


def test_cli_exit_codes(capsys):
    assert distlint_main(["--select", "DL001",
                          os.path.join(FIXTURES, "dl001_good.py")]) == 0
    assert distlint_main(["--select", "DL001",
                          os.path.join(FIXTURES, "dl001_bad.py")]) == 1
    assert distlint_main(["--select", "DL999", "tools"]) == 2
    assert distlint_main(["--list-rules"]) == 0
    capsys.readouterr()


def test_cli_module_entry_point():
    """`python -m tools.distlint` works from the repo root (no jax)."""
    out = subprocess.run(
        [sys.executable, "-m", "tools.distlint", "--list-rules"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for rid in RULE_IDS:
        assert rid in out.stdout


# --------------------------------------------- review-found regressions
def test_dl004_factory_host_side_build_code_is_not_flagged(tmp_path):
    """jit(make_step(...)) traces what the factory RETURNS; the factory's
    own body is host-side build code and may print/time freely."""
    path = _write(tmp_path, (
        "import time\n"
        "import jax\n"
        "def make_step(cfg):\n"
        "    print('building', cfg)\n"          # host side: legal
        "    t0 = time.time()\n"                # host side: legal
        "    def step(state, batch):\n"
        "        print('stepping')\n"           # traced: flagged
        "        return state\n"
        "    return step\n"
        "train = jax.jit(make_step(1))\n"))
    res = lint_files([path], select=["DL004"])
    assert len(res.findings) == 1, [f.render() for f in res.findings]
    assert res.findings[0].line == 7


def test_dl001_function_defined_under_guard_is_not_flagged(tmp_path):
    """A function merely DEFINED under a divergent guard may be called on
    every host — only calls executing under the guard are hazards."""
    path = _write(tmp_path, (
        "import jax\n"
        "def setup():\n"
        "    if jax.process_index() == 0:\n"
        "        def helper(x):\n"
        "            return jax.lax.psum(x, 'data')\n"
        "        return helper\n"
        "    return None\n"))
    res = lint_files([path], select=["DL001"])
    assert res.findings == [], [f.render() for f in res.findings]


def test_dl001_guarded_return_inside_with_block_propagates(tmp_path):
    """A process_index-guarded early return inside a with/try block makes
    the code after that block host-divergent too."""
    path = _write(tmp_path, (
        "import jax\n"
        "def save(state, sharding, batch, f):\n"
        "    with open(f) as fh:\n"
        "        if jax.process_index() != 0:\n"
        "            return None\n"
        "    from tpu_dist.data import assemble_global\n"
        "    return assemble_global(sharding, batch)\n"))
    res = lint_files([path], select=["DL001"])
    assert [f.rule for f in res.findings] == ["DL001"]


def test_dl003_axis_index_first_positional_arg(tmp_path):
    path = _write(tmp_path, (
        "import jax\n"
        "def idx():\n"
        "    good = jax.lax.axis_index('data')\n"
        "    return good + jax.lax.axis_index('modle')\n"))
    res = lint_files([path], select=["DL003"])
    assert len(res.findings) == 1 and "modle" in res.findings[0].message


def test_dl005_stdlib_rng_through_alias_and_from_import(tmp_path):
    path = _write(tmp_path, (
        "import random as rnd\n"
        "from random import randint\n"
        "def draw():\n"
        "    return rnd.random() + randint(0, 3)\n"))
    res = lint_files([path], select=["DL005"])
    assert len(res.findings) == 2, [f.render() for f in res.findings]


def test_shim_check_file_honors_dl006_suppressions(tmp_path):
    from tools.check_ledger_schema import check_file, load_schema
    p = tmp_path / "emits.py"
    p.write_text(
        "ledger.emit('bogus', x=1)  "
        "# distlint: disable=DL006 -- test: deliberately undeclared\n"
        "ledger.emit('also_bogus', x=1)\n")
    out = check_file(str(p), load_schema(), "emits.py")
    assert len(out) == 1 and "also_bogus" in out[0]


def test_trailing_suppression_on_multiline_statement(tmp_path):
    """A formatter may wrap the flagged call across lines, leaving the
    trailing comment on a continuation line; the suppression must still
    cover the whole statement (findings anchor to the first line)."""
    path = _write(tmp_path, (
        "import jax\n"
        "def train_epoch(it, step, state):\n"
        "    for b in it:\n"
        "        state, m = step(state, b)\n"
        "        jax.device_get(\n"
        "            m)  # distlint: disable=DL002 -- test: deliberate sync\n"
        "    return state\n"))
    res = lint_files([path], select=["DL002"])
    assert res.findings == [] and len(res.suppressed) == 1


def test_dl002_closure_seam_pair():
    """The old false negative (satellite of PR 8): a .item() inside a
    nested def called from the hot loop escaped the lexical scan; the
    reachability pass flags it, and the queue-then-drain twin stays
    silent."""
    bad = lint_files([os.path.join(FIXTURES, "dl002_closure_bad.py")],
                     select=["DL002"])
    assert len(bad.findings) == 1, [f.render() for f in bad.findings]
    assert ".item()" in bad.findings[0].message
    assert "reachable" in bad.findings[0].message
    good = lint_files([os.path.join(FIXTURES, "dl002_closure_good.py")],
                      select=["DL002"])
    assert good.findings == [], [f.render() for f in good.findings]


def test_dl201_branch_order_pair():
    """PR 18's source-level MPI-matching prover: cond/switch branches
    whose ordered collective sequences diverge are flagged (helper refs
    resolve through the call graph, lambdas and partial() heads inline),
    while identical sequences, collective-free branches (the pp.py
    gating shape), the padded-zero-operand fix, and dynamically built
    branch lists all stay silent."""
    bad = lint_files([os.path.join(FIXTURES, "dl201_bad.py")],
                     select=["DL201"])
    assert len(bad.findings) == 4, [f.render() for f in bad.findings]
    msgs = [f.message for f in bad.findings]
    # the asymmetric-order shape renders BOTH sequences, in order
    assert any("psum(data) -> pmax(data)" in m
               and "pmax(data) -> psum(data)" in m for m in msgs), msgs
    # the one-armed shape names the silent arm explicitly
    assert any("[no collectives]" in m for m in msgs)
    good = lint_files([os.path.join(FIXTURES, "dl201_good.py")],
                      select=["DL201"])
    assert good.findings == [], [f.render() for f in good.findings]
    # the real pipeline engine leans on per-device lax.cond gating with
    # collectives hoisted OUTSIDE the cond — it must stay clean
    shipped = lint_files([os.path.join("tpu_dist", "parallel", "pp.py")],
                         select=["DL201"])
    assert shipped.findings == [], [f.render() for f in shipped.findings]


def test_dl003_serve_era_spellings_pair():
    """Satellite of PR 18: the axis authority extends to the serving /
    spec-decode spellings added since PR 8 — mesh.shape["axis"] string
    subscripts and axis_size() first-positional axis names — while int
    array-.shape subscripts and dynamic keys stay silent."""
    bad = lint_files([os.path.join(FIXTURES, "dl003_serve_bad.py")],
                     select=["DL003"])
    assert len(bad.findings) == 2, [f.render() for f in bad.findings]
    assert any("mesh.shape[...]" in f.message and "modle" in f.message
               for f in bad.findings)
    assert any("axis_size()" in f.message and "dataa" in f.message
               for f in bad.findings)
    good = lint_files([os.path.join(FIXTURES, "dl003_serve_good.py")],
                      select=["DL003"])
    assert good.findings == [], [f.render() for f in good.findings]


def test_dl003_sp_axis_spellings_pair():
    """Satellite of PR 19: the 'sp' serving-sequence-parallel axis joined
    the parallel/mesh.py authority, so the sharded-pool call-site shapes
    (gather psum, axis_index ownership tests, mesh.shape sizing, arena
    PartitionSpec) lint clean when spelled 'sp' and fire on every typo."""
    bad = lint_files([os.path.join(FIXTURES, "dl003_sp_bad.py")],
                     select=["DL003"])
    assert len(bad.findings) == 4, [f.render() for f in bad.findings]
    for typo in ("spp", "sp_serve", "sq", "spd"):
        assert any(typo in f.message for f in bad.findings), typo
    good = lint_files([os.path.join(FIXTURES, "dl003_sp_good.py")],
                      select=["DL003"])
    assert good.findings == [], [f.render() for f in good.findings]


def test_dl101_pr5_ledger_sigterm_regression():
    """THE acceptance fixture: the PR-5 plain-Lock-in-SIGTERM-handler
    deadlock shape is flagged, and the shipped RLock fix shape is not —
    both as fixtures and in the real tree (obs/ledger.py)."""
    bad = lint_files([os.path.join(FIXTURES, "dl101_bad.py")],
                     select=["DL101"])
    assert len(bad.findings) == 1, [f.render() for f in bad.findings]
    assert "RLock" in bad.findings[0].message
    good = lint_files([os.path.join(FIXTURES, "dl101_good.py")],
                      select=["DL101"])
    assert good.findings == [], [f.render() for f in good.findings]
    shipped = lint_files([os.path.join("tpu_dist", "obs", "ledger.py"),
                          os.path.join("tpu_dist", "obs", "goodput.py"),
                          os.path.join("tpu_dist", "obs", "metrics.py")],
                         select=["DL101"])
    assert shipped.findings == [], [f.render() for f in shipped.findings]


# ------------------------------------------------------------- call graph
def test_callgraph_typed_attribute_resolution():
    """RunObs.__init__ assigns self.goodput = GoodputMonitor(...), so the
    SIGTERM handler's run_end -> self.goodput.emit_goodput chain resolves
    precisely — the edge the PR-5-class deadlock detection rides."""
    g = load_callgraph()
    hr = g.handler_reachable()
    assert "tpu_dist/obs/__init__.py::RunObs.run_end" in hr
    assert "tpu_dist/obs/goodput.py::GoodputMonitor.emit_goodput" in hr
    assert "tpu_dist/obs/ledger.py::Ledger.emit" in hr
    # watchdog pause/resume are NOT on the handler path: precision check
    assert "tpu_dist/obs/watchdog.py::Watchdog.pause" not in hr


def test_callgraph_jit_factory_fixpoint():
    """The plan compiler's entries return jax.jit(...) products through a
    chain of plain returns (compile_train_step -> _lower_train -> a
    lowering -> _jit_gspmd), so they are factories, the trainers'
    self.train_step = compile_train_step(plan, binds) resolves to a
    traced handle, and the engines' loops derive as hot without any
    hard-coded list."""
    g = load_callgraph()
    fac = g._jit_factories()
    for entry in ("compile_train_step", "compile_eval_step"):
        assert f"tpu_dist/plan/compile.py::{entry}" in fac, entry
    rt = g.reaches_traced()
    for fn in ("train_epoch", "_train_epoch_windowed", "_fit_epochs",
               "validate"):
        assert f"tpu_dist/engine/loop.py::Trainer.{fn}" in rt, fn
        assert f"tpu_dist/engine/lm_loop.py::LMTrainer.{fn}" in rt, fn


def test_callgraph_alias_and_import_resolution(tmp_path):
    """import-alias and from-import heads resolve; an out-of-surface file
    is added for the query and removed afterwards (isolation)."""
    p = tmp_path / "snippet.py"
    p.write_text(
        "from tpu_dist.engine.checkpoint import save_checkpoint\n"
        "import tpu_dist.engine.checkpoint as ck\n"
        "def a():\n"
        "    save_checkpoint('d', None, 0, 0.0, 'x', False)\n"
        "def b():\n"
        "    ck.wait_for_async_save()\n")
    g = load_callgraph()
    import ast
    rel = os.path.relpath(str(p), g.root).replace(os.sep, "/")
    added = g.ensure_file(rel, tree=ast.parse(p.read_text()))
    try:
        node_a = g.funcs[f"{rel}::a"]
        targets, _ = g.resolve(node_a, "save_checkpoint")
        assert targets == (
            "tpu_dist/engine/checkpoint.py::save_checkpoint",)
        node_b = g.funcs[f"{rel}::b"]
        targets, _ = g.resolve(node_b, "ck.wait_for_async_save")
        assert targets == (
            "tpu_dist/engine/checkpoint.py::wait_for_async_save",)
    finally:
        if added:
            g.remove_file(rel)
    assert f"{rel}::a" not in g.funcs   # isolation: no leak into the graph


def test_fallback_never_resolves_into_overlay_files():
    """Order independence: by-name fallback from a BASE file must not
    land in a fixture overlay's methods, or a fixture's finding count
    would depend on which edges were cached first (review-found bug: the
    untyped `self._ledger.emit` fallback linked GoodputMonitor into the
    DL101 fixture's Recorder.emit, doubling its findings when the
    fixture was linted in a fresh process)."""
    fix = os.path.join(FIXTURES, "dl101_bad.py")
    first = lint_files([fix], select=["DL101"])
    lint_files(["tpu_dist/obs"])          # populate base edge caches
    again = lint_files([fix], select=["DL101"])
    assert len(first.findings) == len(again.findings) == 1, (
        [f.render() for f in first.findings],
        [f.render() for f in again.findings])


def test_self_referential_local_assignment_does_not_recurse(tmp_path):
    """Review-found crash: `x = x()` (or a=b(); b=a()) made resolve()/
    _resolve_bare() mutually recurse without bound, killing the whole
    lint run with RecursionError via DL002's edge computation."""
    p = tmp_path / "selfref.py"
    p.write_text(
        "import jax\n"
        "step = jax.jit(lambda s: s)\n"
        "def weird():\n"
        "    x = x()\n"
        "    a = b()\n"
        "    b = a()\n"
        "    for _ in range(3):\n"
        "        step(x)\n"
        "        a()\n")
    res = lint_files([str(p)], select=["DL002"])   # must not crash
    assert isinstance(res.findings, list)


def test_remove_file_clears_class_attr_tables(tmp_path):
    """Review-found leak: the attr tables key on ((rel, cls), attr), so
    the old `k[0] == rel` filter never matched and overlay lock/type
    entries survived removal — stale DL101 classifications on re-lint."""
    import ast
    p = tmp_path / "locky.py"
    p.write_text(
        "import threading\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.helper = R()\n")
    g = load_callgraph()
    rel = os.path.relpath(str(p), g.root).replace(os.sep, "/")
    added = g.ensure_file(rel, tree=ast.parse(p.read_text()))
    assert added
    assert any(k[0][0] == rel for k in g.lock_attrs)
    g.remove_file(rel)
    assert not any(k[0][0] == rel for k in g.lock_attrs)
    assert not any(k[0][0] == rel for k in g.attr_types)
    assert not any(k[0][0] == rel for k in g.attr_assign_calls)


def test_dl101_class_attribute_lock_form(tmp_path):
    """Review-found blind spot: `_lock = threading.Lock()` declared in
    the CLASS BODY (not __init__) was recorded as a module-local
    variable, so DL101 went silent on that spelling of the exact PR-5
    deadlock shape."""
    with open(os.path.join(FIXTURES, "dl101_bad.py")) as f:
        src = f.read()
    lines = src.replace(
        "self._lock = threading.Lock()", "pass").splitlines()
    at = next(i for i, l in enumerate(lines) if l.startswith("class "))
    lines.insert(at + 1, "    _lock = threading.Lock()   # class-attr form")
    p = tmp_path / "cls_lock_bad.py"
    p.write_text("\n".join(lines) + "\n")
    res = lint_files([str(p)], select=["DL101"])
    assert len(res.findings) == 1, [f.render() for f in res.findings]
    assert "RLock" in res.findings[0].message


def test_ensure_file_reindexes_changed_source(tmp_path):
    """Review-found staleness: the process-cached graph ignored the
    fresh tree when a rel was already indexed, so a same-process re-lint
    of a file that changed on disk served facts — and finding line
    numbers — from the old parse."""
    import ast
    g = load_callgraph()
    p = tmp_path / "w.py"
    src1 = ("import threading\n"
            "class R:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n")
    rel = os.path.relpath(str(p), g.root).replace(os.sep, "/")
    added = g.ensure_file(rel, tree=ast.parse(src1), src=src1)
    assert added
    try:
        assert g.lock_attrs.get(((rel, "R"), "_lock")) == "Lock"
        src2 = src1.replace("threading.Lock()", "threading.RLock()")
        # same rel, changed content: re-indexed in place, still an
        # overlay owned by the original caller (returns False)
        assert g.ensure_file(rel, tree=ast.parse(src2), src=src2) is False
        assert g.lock_attrs.get(((rel, "R"), "_lock")) == "RLock"
        assert rel in g.overlay_files
        # unchanged content: cheap no-op, no version bump
        v = g._version
        g.ensure_file(rel, tree=ast.parse(src2), src=src2)
        assert g._version == v
    finally:
        g.remove_file(rel)
    assert not any(k[0][0] == rel for k in g.lock_attrs)


def test_callgraph_cycle_tolerance(tmp_path):
    """Mutually recursive functions must not hang reachability."""
    p = tmp_path / "cyc.py"
    p.write_text(
        "import signal\n"
        "def ping():\n"
        "    pong()\n"
        "def pong():\n"
        "    ping()\n"
        "def handler(s, f):\n"
        "    ping()\n"
        "signal.signal(signal.SIGTERM, handler)\n")
    g = load_callgraph()
    import ast
    rel = os.path.relpath(str(p), g.root).replace(os.sep, "/")
    added = g.ensure_file(rel, tree=ast.parse(p.read_text()))
    try:
        reach = g.reachable_from([f"{rel}::handler"])
        assert {f"{rel}::handler", f"{rel}::ping", f"{rel}::pong"} <= reach
    finally:
        if added:
            g.remove_file(rel)


def test_dl001_tensor_rank_comparison_is_not_divergent(tmp_path):
    path = _write(tmp_path, (
        "import jax\n"
        "def reduce_if_matrix(t, x):\n"
        "    if t.rank == 2:\n"                    # tensor rank, not process
        "        return jax.lax.psum(x, 'data')\n"
        "    return x\n"
        "def main_only(rank, sharding, batch):\n"
        "    from tpu_dist.data import assemble_global\n"
        "    if rank == 0:\n"                      # bare rank: process guard
        "        return assemble_global(sharding, batch)\n"))
    res = lint_files([path], select=["DL001"])
    assert len(res.findings) == 1 and res.findings[0].line == 9


# ------------------------------------------------------- tree invariants
def test_mesh_axes_authority_loaded():
    axes = load_mesh_axes()
    assert {"data", "fsdp", "model", "seq", "stage", "expert"} <= axes


def test_tree_is_clean():
    """THE tier-1 pin: zero unsuppressed findings across the FULL
    acceptance surface — tpu_dist, tools (the linter lints itself),
    tests, scripts — with ALL rules (old + DL007 + DL1xx), and
    every suppression carries a reason."""
    res = _full_lint()
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    for finding, sup in res.suppressed:
        assert sup.reason.strip(), finding.render()


# ------------------------------------------------- SARIF / severity / debt
def test_sarif_minimal_schema_shape():
    """`--format sarif` emits valid minimal SARIF 2.1.0: version, one
    run, the rule catalog as tool metadata, results with 1-based
    regions."""
    res = lint_files([os.path.join(FIXTURES, "dl003_bad.py")],
                     select=["DL003"])
    doc = to_sarif(res)
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-2.1.0.json")
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "distlint"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert set(RULE_IDS) | {"DL000"} <= rule_ids
    assert len(run["results"]) == len(res.findings) == 3
    for r in run["results"]:
        assert r["ruleId"] == "DL003"
        assert r["level"] == "error"
        assert r["message"]["text"]
        region = r["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1
        uri = r["locations"][0]["physicalLocation"]["artifactLocation"]
        assert uri["uri"].endswith("dl003_bad.py")


def test_sarif_golden_snapshot():
    """Byte-level SARIF pin (satellite of PR 18): the structural checks
    above can't catch a field rename or an ordering regression that
    still satisfies the schema — CI dashboards parse these artifacts, so
    the exact serialization is contract. Regenerate deliberately with:
    python -c "import json,os; from tools.distlint import lint_files; \\
    from tools.distlint.report import to_sarif; print(json.dumps(
    to_sarif(lint_files([os.path.join('tests','fixtures','distlint',
    'dl003_bad.py')], select=['DL003'])), indent=2, sort_keys=True))"
    """
    res = lint_files([os.path.join(FIXTURES, "dl003_bad.py")],
                     select=["DL003"])
    got = json.dumps(to_sarif(res), indent=2, sort_keys=True) + "\n"
    with open(os.path.join(FIXTURES, "golden_dl003.sarif.json")) as f:
        want = f.read()
    assert got == want, ("SARIF serialization drifted from the golden "
                         "snapshot — if intentional, regenerate "
                         "tests/fixtures/distlint/golden_dl003.sarif.json")


def test_sarif_cli_and_artifact(tmp_path, capsys):
    out_file = str(tmp_path / "distlint.sarif")
    rc = distlint_main(["--format", "sarif", "--sarif-out", out_file,
                        "--select", "DL001",
                        os.path.join(FIXTURES, "dl001_bad.py")])
    assert rc == 1   # error-tier findings still gate
    stdout_doc = json.loads(capsys.readouterr().out)
    with open(out_file) as f:
        file_doc = json.load(f)
    assert stdout_doc == file_doc
    assert len(file_doc["runs"][0]["results"]) == 2


def test_severity_tiers_gate_errors_only(capsys):
    """warn-tier findings (DL102/DL103) print but exit 0; error-tier
    exits 1 — the contract scripts/lint.sh gates on."""
    assert severity_of("DL101") == "error"
    assert severity_of("DL102") == "warn"
    assert severity_of("DL103") == "warn"
    assert severity_of("DL000") == "error"
    rc_warn = distlint_main(["--select", "DL103",
                             os.path.join(FIXTURES, "dl103_bad.py")])
    out = capsys.readouterr().out
    assert rc_warn == 0
    assert "0 error(s), 2 warning(s)" in out
    rc_err = distlint_main(["--select", "DL101",
                            os.path.join(FIXTURES, "dl101_bad.py")])
    capsys.readouterr()
    assert rc_err == 1


def test_debt_inventory(tmp_path, capsys):
    """--debt inventories suppressions: per-rule counts, reasons, and
    staleness (a pin matching no finding is deletable debt)."""
    p = tmp_path / "pinned.py"
    p.write_text(
        "import jax\n"
        "train_step = jax.jit(lambda s, b: s)\n"
        "def train_epoch(it, state):\n"
        "    for b in it:\n"
        "        state, m = train_step(state, b)\n"
        "        jax.device_get(m)  "
        "# distlint: disable=DL002 -- test: deliberate sync\n"
        "    return state\n"
        "x = 1  # distlint: disable=DL005 -- stale: nothing to suppress\n")
    res = lint_files([str(p)])
    debt = collect_debt([str(p)], root=REPO_ROOT, result=res)
    assert debt["by_rule"] == {"DL002": 1, "DL005": 1}
    by_line = {e["line"]: e for e in debt["entries"]}
    active = by_line[6]
    stale = by_line[8]
    assert active["stale"] is False
    assert active["reason"] == "test: deliberate sync"
    assert stale["stale"] is True
    assert debt["stale"] == [stale]
    # CLI: advisory (exit 0) in both formats
    rc = distlint_main(["--debt", str(p)])
    out = capsys.readouterr().out
    assert rc == 0 and "2 suppression(s)" in out and "STALE" in out
    rc = distlint_main(["--debt", "--format", "json", str(p)])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["by_rule"] == {"DL002": 1, "DL005": 1}


def test_debt_real_tree_has_no_stale_pins():
    """Every suppression in the tree matches a live finding — a pin that
    suppresses nothing is debt to delete, caught here not in review."""
    res = _full_lint()
    debt = collect_debt(SURFACE, root=REPO_ROOT, result=res,
                        with_ages=False)   # counts/staleness only: cheap
    assert debt["entries"], "expected the tree's reasoned pins"
    stale = [f"{e['path']}:{e['line']}" for e in debt["stale"]]
    assert not stale, f"stale suppressions (nothing to suppress): {stale}"


def test_dl007_rebind_and_branch_shapes(tmp_path):
    p = tmp_path / "donate.py"
    p.write_text(
        "import jax\n"
        "f = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "def good(state, batches):\n"
        "    for b in batches:\n"
        "        state = f(state, b)\n"       # rebind every iteration
        "    return state\n"
        "def bad(state, b):\n"
        "    out = f(state, b)\n"
        "    return out, state.step\n")       # reads the donated buffer
    res = lint_files([str(p)], select=["DL007"])
    assert len(res.findings) == 1, [x.render() for x in res.findings]
    assert res.findings[0].line == 9


def test_dl007_multiline_call_and_same_line_read(tmp_path):
    """Ordering is positional, not line-based: args on continuation
    lines of a multi-line donating call are NOT post-donation reads,
    while a same-line read past the closing paren IS."""
    p = tmp_path / "donate_pos.py"
    p.write_text(
        "import jax\n"
        "f: object = jax.jit(lambda s, b: s, donate_argnums=(0,))\n"
        "def ok(state, batch):\n"
        "    out = f(\n"
        "        state,\n"                    # inside the call span
        "        batch)\n"
        "    return out\n"
        "def bad(state, b):\n"
        "    return f(state, b), state.step\n")   # read after the paren
    res = lint_files([str(p)], select=["DL007"])
    assert len(res.findings) == 1, [x.render() for x in res.findings]
    assert res.findings[0].line == 9


def test_dl101_annotated_lock_attr(tmp_path):
    """`self._lock: threading.Lock = threading.Lock()` (AnnAssign) feeds
    lock_attrs exactly like the plain assign — the deadlock gate must
    not disappear when someone adds type annotations."""
    p = tmp_path / "ann_lock.py"
    p.write_text(
        "import signal\n"
        "import threading\n"
        "class Recorder:\n"
        "    def __init__(self):\n"
        "        self._lock: threading.Lock = threading.Lock()\n"
        "        self._rows: list = []\n"
        "        signal.signal(signal.SIGTERM, self._on_sigterm)\n"
        "    def emit(self, row):\n"
        "        with self._lock:\n"
        "            self._rows.append(row)\n"
        "    def finalize(self):\n"
        "        with self._lock:\n"
        "            self._rows.append('end')\n"
        "    def _on_sigterm(self, signum, frame):\n"
        "        self.finalize()\n")
    res = lint_files([str(p)], select=["DL101"])
    assert len(res.findings) == 1, [x.render() for x in res.findings]
    assert "RLock" in res.findings[0].message


def test_cli_debt_with_sarif_out_and_json_purity(tmp_path, capsys):
    """--sarif-out writes its artifact even under --debt, and --with-debt
    keeps machine-readable stdout clean (debt goes to stderr)."""
    out_file = str(tmp_path / "debt.sarif")
    rc = distlint_main(["--debt", "--sarif-out", out_file,
                        "--select", "DL001",
                        os.path.join(FIXTURES, "dl001_bad.py")])
    capsys.readouterr()
    assert rc == 0
    with open(out_file) as f:
        assert json.load(f)["version"] == "2.1.0"
    rc = distlint_main(["--format", "json", "--with-debt",
                        "--select", "DL001",
                        os.path.join(FIXTURES, "dl001_bad.py")])
    cap = capsys.readouterr()
    assert rc == 1
    assert json.loads(cap.out)["errors"] == 2   # stdout: pure JSON
    assert "distlint debt:" in cap.err


def test_dl002_module_level_hot_loop(tmp_path):
    """A top-level step loop is hot (the `<module>` pseudo-node joins
    the lexical scan AND seeds reachability for helpers it calls)."""
    p = tmp_path / "modloop.py"
    p.write_text(
        "import jax\n"
        "step = jax.jit(lambda s, b: s)\n"
        "def log(m):\n"
        "    return m['loss'].item()\n"       # reachable from the loop
        "state = 0\n"
        "for b in range(3):\n"
        "    state, m = step(state, b)\n"
        "    log(m)\n")
    res = lint_files([str(p)], select=["DL002"])
    assert [f.line for f in res.findings] == [4], \
        [x.render() for x in res.findings]


def test_cli_debt_select_does_not_mislabel_stale(capsys):
    """Staleness is only decidable against a full-rule result: under
    --select, live pins for unselected rules must NOT be called stale."""
    rc = distlint_main(["--debt", "--select", "DL001", "tpu_dist"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "STALE" not in out
    assert "distlint debt:" in out


def test_sarif_relative_uris_without_baseid_declaration():
    """Repo-relative artifact URIs with SRCROOT left undeclared (no
    originalUriBaseIds) — consumers resolve against their own checkout;
    declaring file:/// would point results at filesystem root."""
    res = lint_files([os.path.join(FIXTURES, "dl001_bad.py")],
                     select=["DL001"])
    run = to_sarif(res)["runs"][0]
    assert "originalUriBaseIds" not in run
    for r in run["results"]:
        loc = r["locations"][0]["physicalLocation"]["artifactLocation"]
        assert not loc["uri"].startswith("/")


def test_dl104_handler_body_in_file_not_mentioning_signal(tmp_path):
    """A handler whose body lives in an in-surface file that never says
    'signal' (installed from a sibling file) is still body-scanned — the
    text gate defers to the cross-file handler root set. (Out-of-surface
    files overlay one at a time by design, so the pair sits in a tmp
    project surface.)"""
    pkg = tmp_path / "tpu_dist"
    pkg.mkdir()
    (pkg / "handlers.py").write_text(
        "import logging\n"
        "def on_term(signum, frame):\n"
        "    logging.error('terminating')\n")
    (pkg / "installer.py").write_text(
        "import signal\n"
        "from tpu_dist import handlers\n"
        "def install():\n"
        "    signal.signal(signal.SIGTERM, handlers.on_term)\n")
    res = lint_files([str(pkg)], root=str(tmp_path), select=["DL104"])
    msgs = [f.render() for f in res.findings]
    assert any("logging call" in m and "handlers.py" in m for m in msgs), msgs
