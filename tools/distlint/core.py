"""distlint engine: file walking, suppressions, rule running, output.

Stdlib-only by contract (``ast`` + ``tokenize``; no jax import anywhere in
the package): the linter must run in CI containers and pre-commit hooks
that have no accelerator stack, and importing the checked modules would
initialize a backend. Everything the rules need from the repo (mesh axis
names, the ledger event schema) is extracted from SOURCE by AST — the same
trick ``tools/check_ledger_schema.py`` proved out, generalized.

Vocabulary:

* a :class:`Finding` is one violation at ``path:line:col`` with a rule id;
* a suppression is an inline comment ``# distlint: disable=DL002 -- reason``
  (trailing on the flagged line, or standalone on the line above). The
  reason is REQUIRED — a bare disable is itself a finding (DL000), because
  an unexplained suppression is indistinguishable from a stale one;
* :class:`Project` lazily loads cross-file facts (mesh axes, event schema)
  relative to the repo root, so rules stay pure functions of (file, facts).
"""

from __future__ import annotations

import ast
import contextlib
import hashlib
import io
import os
import re
import tokenize
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

MESH_FILE = os.path.join("tpu_dist", "parallel", "mesh.py")
SCHEMA_FILE = os.path.join("tpu_dist", "obs", "ledger.py")

# directory names never entered by the walker (explicit file arguments are
# always linted — that is how the test fixtures get checked without the
# clean-tree sweep tripping over their deliberately bad code)
SKIP_DIRS = {"__pycache__", ".git", "fixtures", "node_modules", ".venv"}

# meta-rule id: malformed suppressions, unparseable files. Not suppressible.
META_RULE = "DL000"

_SUPPRESS_RE = re.compile(
    r"^#\s*distlint:\s*disable=(?P<rules>DL\d{3}(?:\s*,\s*DL\d{3})*)"
    r"(?:\s*--\s*(?P<reason>.*\S))?\s*$")
# directive recognition is anchored: only comments STARTING with
# '# distlint:' are directives, so prose mentioning the tool stays inert
_SUPPRESS_HINT_RE = re.compile(r"^#\s*distlint\s*:")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str       # repo-relative, '/'-separated
    line: int
    col: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"

    def to_json(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}


@dataclass(frozen=True)
class Suppression:
    """A parsed ``# distlint: disable=...`` comment bound to a code line."""
    rules: Tuple[str, ...]
    reason: str
    line: int        # the code line it applies to
    comment_line: int

    def to_json(self) -> dict:
        return {"rules": list(self.rules), "reason": self.reason,
                "line": self.line, "comment_line": self.comment_line}


def _expand_alias(aliases: Dict[str, str], head: str) -> str:
    """Expand the leading import alias of a dotted name against
    ``aliases``: ``np.random.seed`` -> ``numpy.random.seed`` under
    ``import numpy as np``. Unknown heads pass through unchanged. The
    ONE implementation of this semantics — FileContext.resolve and the
    call-graph recorders all route here so they cannot drift."""
    first, sep, rest = head.partition(".")
    target = aliases.get(first, first)
    return target + sep + rest if sep else target


class FileContext:
    """Per-file parse products shared by every rule (one AST, one token
    pass per file — rules never re-read the source)."""

    def __init__(self, path: str, rel: str, src: str):
        self.path = path
        self.rel = rel
        self.src = src
        self.lines = src.splitlines()
        self.tree = ast.parse(src)            # may raise SyntaxError
        self.import_aliases = _import_aliases(self.tree)

    def line_text(self, lineno: int) -> str:
        return self.lines[lineno - 1] if 0 < lineno <= len(self.lines) else ""

    def resolve(self, qualname: str) -> str:
        """Expand the leading import alias of a dotted name:
        ``np.random.seed`` -> ``numpy.random.seed`` under ``import numpy
        as np``; ``random.split`` -> ``jax.random.split`` under ``from jax
        import random``. Unknown heads pass through unchanged."""
        if not qualname:
            return qualname
        return _expand_alias(self.import_aliases, qualname)


def _import_aliases(tree: ast.AST) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.partition(".")[0]] = (
                    a.name if a.asname else a.name.partition(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


# --------------------------------------------------------------- project
class Project:
    """Cross-file facts, loaded lazily from source by AST (never imported)."""

    def __init__(self, root: str = REPO_ROOT):
        self.root = os.path.abspath(root)
        self._mesh_axes: Optional[Set[str]] = None
        self._event_schema: Optional[Dict[str, tuple]] = None

    @property
    def mesh_axes(self) -> Set[str]:
        """Axis-name literals declared as ``*_AXIS = "..."`` in
        tpu_dist/parallel/mesh.py — THE authority DL003 validates against.
        Empty set (file absent) disables DL003 rather than flagging
        everything."""
        if self._mesh_axes is None:
            self._mesh_axes = load_mesh_axes(self.root)
        return self._mesh_axes

    @property
    def event_schema(self) -> Dict[str, tuple]:
        if self._event_schema is None:
            self._event_schema = load_event_schema(self.root)
        return self._event_schema

    @property
    def callgraph(self) -> "CallGraph":
        """The cross-file call graph + reachability engine (lazy,
        process-cached — see :func:`load_callgraph`)."""
        return load_callgraph(self.root)


def load_mesh_axes(root: str = REPO_ROOT) -> Set[str]:
    path = os.path.join(root, MESH_FILE)
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        tree = ast.parse(f.read())
    axes: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Name) and t.id.endswith("_AXIS")
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, str)):
                    axes.add(node.value.value)
    return axes


def load_event_schema(root: str = REPO_ROOT) -> Dict[str, tuple]:
    """EVENT_SCHEMA extracted from ledger.py source — a pure literal by
    that dict's own contract."""
    path = os.path.join(root, SCHEMA_FILE)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "EVENT_SCHEMA":
                    return ast.literal_eval(node.value)
    raise AssertionError(f"EVENT_SCHEMA literal not found in {path}")


# ---------------------------------------------------------- suppressions
def parse_suppressions(src: str) -> Tuple[List[Suppression], List[Tuple[int, str]]]:
    """(suppressions, malformed) from the token stream.

    A trailing comment suppresses its own line; a standalone comment (the
    line holds nothing else) suppresses the next non-blank, non-comment
    line. ``malformed`` is (line, problem) pairs for distlint comments that
    fail the grammar — most importantly a missing ``-- reason``.
    """
    sups: List[Suppression] = []
    malformed: List[Tuple[int, str]] = []
    lines = src.splitlines()
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return sups, malformed
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        text = tok.string
        if not _SUPPRESS_HINT_RE.search(text):
            continue
        lineno = tok.start[0]
        m = _SUPPRESS_RE.search(text)
        if "disable" not in text:
            # other distlint directives don't exist (yet): flag typos like
            # '# distlint: off' instead of silently ignoring them
            malformed.append((lineno, f"unrecognized distlint directive "
                                      f"{text.strip()!r} (only "
                                      "'disable=DLxxx -- reason' exists)"))
            continue
        if m is None or not (m.group("reason") or "").strip():
            malformed.append(
                (lineno, "suppression must carry a reason: "
                         "'# distlint: disable=DLxxx -- <why this is ok>'"))
            continue
        rules = tuple(r.strip() for r in m.group("rules").split(","))
        before = lines[lineno - 1][:tok.start[1]]
        if before.strip():
            target = lineno                       # trailing comment
        else:                                     # standalone: next code line
            target = lineno
            for j in range(lineno + 1, len(lines) + 1):
                s = lines[j - 1].strip()
                if s and not s.startswith("#"):
                    target = j
                    break
        sups.append(Suppression(rules=rules, reason=m.group("reason").strip(),
                                line=target, comment_line=lineno))
    return sups, malformed


# --------------------------------------------------------------- linting
@dataclass
class LintResult:
    findings: List[Finding]
    suppressed: List[Tuple[Finding, Suppression]]
    files_checked: int

    def to_json(self) -> dict:
        return {
            "findings": [f.to_json() for f in self.findings],
            "suppressed": [dict(f.to_json(), reason=s.reason)
                           for f, s in self.suppressed],
            "files_checked": self.files_checked,
        }


def iter_python_files(paths: Sequence[str], root: str) -> List[str]:
    """Expand dirs (recursively, skipping SKIP_DIRS) and keep explicit .py
    file arguments as-is. Paths may be absolute or root-relative."""
    out: List[str] = []
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(full):
            out.append(full)
        elif os.path.isdir(full):
            for dirpath, dirnames, files in os.walk(full):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in SKIP_DIRS)
                out += [os.path.join(dirpath, f) for f in sorted(files)
                        if f.endswith(".py")]
        else:
            raise FileNotFoundError(f"distlint: no such path: {p}")
    seen, uniq = set(), []
    for f in out:
        if f not in seen:
            seen.add(f)
            uniq.append(f)
    return uniq


def lint_files(paths: Sequence[str], root: str = REPO_ROOT,
               select: Optional[Iterable[str]] = None,
               project: Optional[Project] = None) -> LintResult:
    """Run the (selected) rules over every file under ``paths``."""
    from tools.distlint.rules import RULES

    project = project or Project(root)
    selected = [r for r in RULES
                if select is None or r.id in set(select)]
    # one graph overlay add/remove per FILE, not per graph-backed rule:
    # the rules' own graph_scope calls become no-ops (ensure_file is
    # idempotent), so an out-of-surface file is indexed once and the
    # version-keyed reachability memos survive all five rule passes
    needs_graph = any(r.uses_graph for r in selected)
    findings: List[Finding] = []
    suppressed: List[Tuple[Finding, Suppression]] = []
    files = iter_python_files(paths, project.root)
    for path in files:
        rel = os.path.relpath(path, project.root).replace(os.sep, "/")
        with open(path, encoding="utf-8") as f:
            src = f.read()
        # tokenizing is the expensive half of suppression parsing; only
        # files that mention the directive at all need it
        sups, malformed = (parse_suppressions(src)
                           if "distlint" in src else ([], []))
        for line, problem in malformed:
            findings.append(Finding(META_RULE, rel, line, 0, problem))
        try:
            ctx = FileContext(path, rel, src)
        except SyntaxError as e:
            findings.append(Finding(META_RULE, rel, e.lineno or 0, 0,
                                    f"unparseable: {e.msg}"))
            continue
        by_line: Dict[int, List[Suppression]] = {}
        for s in sups:
            # a suppression bound to ANY physical line of a multi-line
            # statement covers the whole statement: findings anchor to the
            # node's first line, while a trailing comment (or a formatter
            # re-wrap) may sit on a continuation line
            for line in _statement_span(ctx.tree, s.line):
                by_line.setdefault(line, []).append(s)
        with (graph_scope(project, ctx) if needs_graph
              else contextlib.nullcontext()):
            for rule in selected:
                for f in rule.check(ctx, project):
                    hit = next((s for s in by_line.get(f.line, ())
                                if f.rule in s.rules), None)
                    if hit is not None:
                        suppressed.append((f, hit))
                    else:
                        findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return LintResult(findings, suppressed, len(files))


def _statement_span(tree: ast.AST, line: int) -> range:
    """Physical-line range of the innermost SIMPLE statement containing
    ``line`` (compound statements — defs, ifs, loops — are skipped: a
    suppression inside one must not blanket its whole body). Falls back to
    the single line itself."""
    best = None
    for node in ast.walk(tree):
        if not isinstance(node, ast.stmt) or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef,
                       ast.If, ast.For, ast.AsyncFor, ast.While, ast.With,
                       ast.AsyncWith, ast.Try)):
            continue
        end = getattr(node, "end_lineno", node.lineno)
        if node.lineno <= line <= end and (
                best is None or node.lineno > best[0]):
            best = (node.lineno, end)
    if best is None:
        return range(line, line + 1)
    return range(best[0], best[1] + 1)


# ------------------------------------------------------------ call graph
# Cross-file reachability engine (stdlib-only, like every other Project
# fact): intra-repo def/call edges extracted by AST with import-alias and
# attribute-type resolution, plus the ROOT SETS the DL1xx concurrency
# rules and DL002's hot-path derivation need — traced (jit/shard_map)
# functions, signal handlers, thread targets, atexit/excepthook hooks, and
# "escaped" callbacks (function references handed to registration calls,
# e.g. ledger sinks). Precision contract: resolution is best-effort and
# DELIBERATELY over-approximate where types are unknown (a method call on
# an untyped receiver falls back to every project method of that name,
# minus a stdlib-noise stoplist); rules built on it must therefore pair a
# reachability condition with a syntactic one (e.g. DL101: *plain* Lock
# AND handler-reachable AND mainline acquire) so over-approximation can
# only widen an already-real hazard, not invent one from nothing.

# the project surface the base graph indexes (missing entries skipped —
# tests build graphs against tmp roots too)
GRAPH_SURFACE = ("tpu_dist", "tools", "scripts", "tests")

# terminal method names excluded from the by-name fallback: they are
# overwhelmingly stdlib container/IO calls, and an edge from every
# `x.get()` to every project method named `get` would drown the graph
_FALLBACK_NOISE = frozenset({
    "append", "extend", "pop", "get", "items", "keys", "values", "join",
    "split", "strip", "startswith", "endswith", "format", "write", "read",
    "flush", "close", "add", "update", "copy", "sort", "index", "count",
    "insert", "remove", "clear", "setdefault", "popitem", "encode",
    "decode", "open", "exists", "put", "start", "wait", "set", "acquire",
    "release", "lower", "upper", "replace", "reshape", "astype", "mean",
    "sum", "min", "max", "item", "tolist", "numpy", "block_until_ready",
})

_TRACER_NAMES = ("jit", "pjit")


def _is_tracer_head(head: str) -> bool:
    t = head.rpartition(".")[2]
    return t in _TRACER_NAMES or "shard_map" in t


class FuncNode:
    """One function/method (or the module pseudo-node ``<module>``) in the
    call graph, with everything resolution needs recorded at build time."""

    __slots__ = (
        "qual", "rel", "name", "cls", "node", "lineno", "parent",
        "children", "calls", "arg_refs", "factory_args", "local_types",
        "local_traced", "local_assign_calls", "lock_acquires", "loops",
        "return_calls", "returns_jit", "return_class", "aliases")

    def __init__(self, qual, rel, name, cls, node, lineno, parent,
                 aliases):
        self.qual = qual
        self.rel = rel
        self.name = name
        self.cls = cls                 # (rel, clsname) or None
        self.node = node               # ast def node (None for <module>)
        self.lineno = lineno
        self.parent = parent           # enclosing FuncNode or None
        self.children: Dict[str, str] = {}       # nested def name -> qual
        self.calls: List[Tuple[str, int]] = []   # (dotted head, lineno)
        self.arg_refs: List[str] = []  # Name/Attribute refs passed as args
        self.factory_args: List[str] = []  # heads of calls whose RESULT is
        #                                    passed as an argument
        self.local_types: Dict[str, tuple] = {}  # var -> (rel, clsname)
        self.local_traced: Set[str] = set()      # var = jax.jit(...)
        self.local_assign_calls: Dict[str, str] = {}  # var -> call head
        self.lock_acquires: List[Tuple[str, str, int, int]] = []
        #   (owner 'self'|'name', attr-or-name, lineno, col)
        self.loops: List[ast.AST] = []   # same-scope For/While statements
        self.return_calls: List[str] = []
        self.returns_jit = False
        self.return_class: Optional[str] = None  # 'ClassName' literal ctor
        self.aliases = aliases         # module import table (shared)


class CallGraph:
    """Lazily built, incrementally extendable cross-file call graph.

    Files inside :data:`GRAPH_SURFACE` are indexed once per process (see
    :func:`load_callgraph`); out-of-surface files (rule fixtures, tmp
    snippets) are added per check via :meth:`ensure_file` and removed
    again with :meth:`remove_file` so tests stay isolated. Derived sets
    (reachability closures) are memoized per graph version."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.funcs: Dict[str, FuncNode] = {}
        self.file_quals: Dict[str, List[str]] = {}       # rel -> quals
        self.file_digest: Dict[str, str] = {}            # rel -> src sha1
        self.module_of: Dict[str, str] = {}              # module name -> rel
        self.module_funcs: Dict[Tuple[str, str], str] = {}
        self.module_traced: Set[Tuple[str, str]] = set()
        self.classes: Dict[tuple, Dict[str, str]] = {}   # clskey -> methods
        self.class_alias: Dict[Tuple[str, str], tuple] = {}  # (rel, name)
        self.methods_by_name: Dict[str, List[str]] = {}
        self.attr_types: Dict[tuple, tuple] = {}     # (clskey, attr) -> cls
        self.attr_assign_calls: Dict[tuple, str] = {}  # (clskey, attr) -> head
        self.attr_traced: Set[tuple] = set()         # (clskey, attr)
        self.lock_attrs: Dict[tuple, str] = {}       # (clskey, attr) -> kind
        self.signal_handler_heads: List[Tuple[str, str]] = []  # (qual, head)
        self.signal_installs: Dict[str, list] = {}   # rel -> install records
        self.thread_ctors: Dict[str, list] = {}      # rel -> ctor records
        self.join_sites: List[Tuple[str, str]] = []  # (qual, receiver tail)
        self.atexit_heads: List[Tuple[str, str]] = []
        self.hook_assign_heads: List[Tuple[str, str]] = []  # sys.excepthook=
        self.decorated_traced: Set[str] = set()
        self.jit_mark_heads: List[Tuple[str, str]] = []  # jit(f) name marks
        self._version = 0
        # files added AFTER the base build (fixtures, tmp snippets): the
        # by-name fallback never resolves INTO them from another file, so
        # base-file edges are identical whether or not an overlay happens
        # to be present (and whenever the edge cache was populated)
        self.overlay_files: Set[str] = set()
        self._base_built = False
        self._edges: Dict[str, Tuple[tuple, bool]] = {}  # qual -> (targets,
        #                                                  dispatches_traced)
        self._memo: Dict[str, Tuple[int, object]] = {}
        # in-flight (node id, head) pairs while following local var
        # assignments: `x = x()` (or mutual a=b(); b=a()) must not send
        # resolve()/_resolve_bare() into unbounded recursion
        self._resolving: Set[Tuple[int, str]] = set()
        # (rel, lineno) -> assignment target of a threading.Thread(...)
        # RHS; statements visit parent-first, so the bind is recorded here
        # before the Call node creates its ctor record and consumes it
        self._pending_thread_binds: Dict[Tuple[str, int], str] = {}

    # -- build ----------------------------------------------------------
    def ensure_file(self, rel: str, tree: Optional[ast.AST] = None,
                    path: Optional[str] = None,
                    src: Optional[str] = None) -> bool:
        """Index one file (idempotent); returns True when it was newly
        added (caller pairs with :meth:`remove_file` for isolation).

        An already-indexed file whose ``src`` digest no longer matches is
        re-indexed in place (same overlay/base status, version bumped):
        the graph is process-cached, so a same-process re-lint of a file
        that changed on disk must not serve facts — or finding line
        numbers — from the stale parse."""
        digest = (hashlib.sha1(src.encode("utf-8", "replace")).hexdigest()
                  if src is not None else None)
        if rel in self.file_quals:
            if digest is None or self.file_digest.get(rel) == digest:
                return False
            if tree is None:
                try:
                    tree = ast.parse(src)
                except SyntaxError:
                    return False
            was_overlay = rel in self.overlay_files
            self.remove_file(rel)
            self._index_file(rel, tree)
            self.file_digest[rel] = digest
            if was_overlay:
                self.overlay_files.add(rel)
            self._version += 1
            return False
        if self._base_built:
            self.overlay_files.add(rel)
        if tree is None and src is None:
            full = path or os.path.join(self.root, rel)
            try:
                with open(full, encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                self.file_quals[rel] = []
                return True
            digest = hashlib.sha1(
                src.encode("utf-8", "replace")).hexdigest()
        if tree is None:
            try:
                tree = ast.parse(src)
            except SyntaxError:
                self.file_quals[rel] = []
                return True
        self._index_file(rel, tree)
        if digest is not None:
            self.file_digest[rel] = digest
        self._version += 1
        return True

    def remove_file(self, rel: str) -> None:
        self.overlay_files.discard(rel)
        self.file_digest.pop(rel, None)
        quals = self.file_quals.pop(rel, None)
        if quals is None:
            return
        for q in quals:
            n = self.funcs.pop(q, None)
            self._edges.pop(q, None)
            if n is not None and n.cls is not None:
                lst = self.methods_by_name.get(n.name)
                if lst and q in lst:
                    lst.remove(q)
        # module_funcs/class_alias key on (rel, name); the attr tables key
        # on ((rel, cls), attr) — filter each by ITS rel component
        for d in (self.module_funcs, self.class_alias):
            for k in [k for k in d if k[0] == rel]:
                del d[k]
        for d in (self.attr_types, self.attr_assign_calls, self.lock_attrs):
            for k in [k for k in d if k[0][0] == rel]:
                del d[k]
        self.module_traced = {k for k in self.module_traced if k[0] != rel}
        self.attr_traced = {k for k in self.attr_traced if k[0][0] != rel}
        self.classes = {k: v for k, v in self.classes.items() if k[0] != rel}
        self.module_of = {m: r for m, r in self.module_of.items()
                          if r != rel}
        self.signal_installs.pop(rel, None)
        self.thread_ctors.pop(rel, None)
        for lst_name in ("signal_handler_heads", "atexit_heads",
                         "hook_assign_heads", "jit_mark_heads",
                         "join_sites"):
            setattr(self, lst_name,
                    [t for t in getattr(self, lst_name)
                     if not t[0].startswith(rel + "::")])
        self.decorated_traced = {q for q in self.decorated_traced
                                 if not q.startswith(rel + "::")}
        self._version += 1

    def _module_name(self, rel: str) -> str:
        mod = rel[:-3] if rel.endswith(".py") else rel
        if mod.endswith("/__init__"):
            mod = mod[:-len("/__init__")]
        return mod.replace("/", ".")

    def _index_file(self, rel: str, tree: ast.AST) -> None:
        aliases = _import_aliases(tree)
        self.module_of[self._module_name(rel)] = rel
        quals: List[str] = []
        expr_calls: Set[int] = set()   # id(call) used as a bare statement
        for n in ast.walk(tree):
            if isinstance(n, ast.Expr) and isinstance(n.value, ast.Call):
                expr_calls.add(id(n.value))

        mod_node = FuncNode(f"{rel}::<module>", rel, "<module>", None,
                            None, 0, None, aliases)
        self.funcs[mod_node.qual] = mod_node
        quals.append(mod_node.qual)

        def visit_scope(owner: FuncNode, stmts, cls: Optional[tuple]):
            """Walk one runtime scope: nested defs become new nodes, class
            bodies recurse with the class key, everything else feeds the
            owner's call/assign records."""
            stack = list(stmts)
            while stack:
                s = stack.pop()
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._add_func(owner, s, cls, rel, aliases, quals,
                                   expr_calls)
                    continue
                if isinstance(s, ast.ClassDef):
                    clskey = (rel, s.name)
                    self.classes.setdefault(clskey, {})
                    self.class_alias[(rel, s.name)] = clskey
                    visit_scope(owner, s.body, clskey)
                    continue
                if isinstance(s, ast.Lambda):
                    continue
                self._record_stmt(owner, s, cls, expr_calls)
                stack.extend(ast.iter_child_nodes(s))

        visit_scope(mod_node, tree.body, None)
        self.file_quals[rel] = quals

    def _add_func(self, parent: FuncNode, fn, cls, rel, aliases, quals,
                  expr_calls) -> None:
        if parent.name == "<module>" and cls is None:
            qual = f"{rel}::{fn.name}"
        elif cls is not None and parent.name == "<module>":
            qual = f"{rel}::{cls[1]}.{fn.name}"
        else:
            qual = f"{parent.qual}.<locals>.{fn.name}"
        node = FuncNode(qual, rel, fn.name, cls, fn, fn.lineno,
                        parent, aliases)
        self.funcs[qual] = node
        quals.append(qual)
        parent.children[fn.name] = qual
        if cls is not None:
            self.classes.setdefault(cls, {})[fn.name] = qual
            self.methods_by_name.setdefault(fn.name, []).append(qual)
        elif parent.name == "<module>":
            self.module_funcs[(rel, fn.name)] = qual
        for d in fn.decorator_list:
            if self._deco_is_tracer(d):
                self.decorated_traced.add(qual)

        def visit(stmts, in_cls):
            stack = list(stmts)
            while stack:
                s = stack.pop()
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._add_func(node, s, in_cls, rel, aliases, quals,
                                   expr_calls)
                    continue
                if isinstance(s, ast.ClassDef):
                    clskey = (rel, f"{fn.name}.<locals>.{s.name}")
                    self.classes.setdefault(clskey, {})
                    visit(s.body, clskey)
                    continue
                if isinstance(s, ast.Lambda):
                    continue
                self._record_stmt(node, s, in_cls or cls, expr_calls)
                stack.extend(ast.iter_child_nodes(s))

        visit(fn.body, cls)
        # `return f` where f was bound to jit(...) earlier in the body
        for n2 in ast.walk(fn):
            if isinstance(n2, ast.Return) and isinstance(n2.value, ast.Name):
                if n2.value.id in node.local_traced:
                    node.returns_jit = True

    def _deco_is_tracer(self, d: ast.AST) -> bool:
        if isinstance(d, ast.Call):
            if terminal_name(d.func) == "partial":
                return any(self._deco_is_tracer(a) for a in d.args[:1])
            return self._deco_is_tracer(d.func)
        return _is_tracer_head(dotted_name(d) or terminal_name(d))

    def _record_stmt(self, node: FuncNode, s: ast.AST, cls, expr_calls):
        """Record the facts one (possibly nested) expression/statement
        contributes: calls, assignments, lock acquires, registrations."""
        if isinstance(s, ast.Call):
            head = dotted_name(s.func)
            node.calls.append((head, getattr(s, "lineno", 0)))
            resolved_head = _expand_alias(node.aliases, head)
            tname = terminal_name(s.func)
            # registrations whose argument is a callable reference
            if resolved_head == "signal.signal" and len(s.args) >= 2:
                h = dotted_name(s.args[1])
                if h:
                    self.signal_handler_heads.append((node.qual, h))
                self.signal_installs.setdefault(node.rel, []).append({
                    "qual": node.qual, "lineno": s.lineno,
                    "col": s.col_offset, "handler": s.args[1],
                    "result_used": id(s) not in expr_calls})
            elif resolved_head == "threading.Thread":
                kw = {k.arg: k.value for k in s.keywords}
                daemon = kw.get("daemon")
                target = kw.get("target")
                self.thread_ctors.setdefault(node.rel, []).append({
                    "qual": node.qual, "lineno": s.lineno,
                    "col": s.col_offset,
                    "daemon_true": isinstance(daemon, ast.Constant)
                    and daemon.value is True,
                    "target_head": dotted_name(target) if target else "",
                    "bind": self._pending_thread_binds.pop(
                        (node.rel, s.lineno), None)})
            elif resolved_head == "atexit.register" and s.args:
                h = dotted_name(s.args[0])
                if h:
                    self.atexit_heads.append((node.qual, h))
            elif tname in _TRACER_NAMES and s.args \
                    and isinstance(s.args[0], ast.Name):
                self.jit_mark_heads.append((node.qual, s.args[0].id))
            if tname == "acquire" and isinstance(s.func, ast.Attribute):
                self._record_lock_ref(node, s.func.value, s)
            if tname == "join" and isinstance(s.func, ast.Attribute):
                recv = terminal_name(s.func.value)
                if recv:
                    self.join_sites.append((node.qual, recv))
            # callable references escaping through arguments
            for a in list(s.args) + [k.value for k in s.keywords]:
                if isinstance(a, (ast.Name, ast.Attribute)):
                    h = dotted_name(a)
                    if h and h != "self":
                        node.arg_refs.append(h)
                elif isinstance(a, ast.Call):
                    h = dotted_name(a.func)
                    if h:
                        node.factory_args.append(h)
        elif isinstance(s, (ast.For, ast.While)):
            node.loops.append(s)
        elif isinstance(s, ast.With) or isinstance(s, ast.AsyncWith):
            for item in s.items:
                if isinstance(item.context_expr, (ast.Name, ast.Attribute)):
                    self._record_lock_ref(node, item.context_expr, s)
        elif isinstance(s, ast.Assign) and len(s.targets) == 1:
            self._record_assign(node, s.targets[0], s.value, cls)
        elif isinstance(s, ast.AnnAssign) and s.value is not None:
            # `self._lock: threading.Lock = threading.Lock()` must feed
            # lock_attrs/attr_types exactly like the unannotated form
            self._record_assign(node, s.target, s.value, cls)
        elif isinstance(s, ast.Return) and s.value is not None:
            if isinstance(s.value, ast.Call):
                head = dotted_name(s.value.func)
                node.return_calls.append(head)
                if _is_tracer_head(head):
                    node.returns_jit = True
                elif isinstance(s.value.func, ast.Name):
                    node.return_class = s.value.func.id

    def _record_lock_ref(self, node: FuncNode, expr: ast.AST, at) -> None:
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self":
            node.lock_acquires.append(("self", expr.attr, at.lineno,
                                       at.col_offset))
        elif isinstance(expr, ast.Name):
            node.lock_acquires.append(("name", expr.id, at.lineno,
                                       at.col_offset))

    def _record_assign(self, node: FuncNode, tgt, value, cls) -> None:
        is_self_attr = (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self" and cls is not None)
        # sys.excepthook = handler
        if isinstance(tgt, ast.Attribute) and dotted_name(tgt) in (
                "sys.excepthook", "threading.excepthook"):
            h = dotted_name(value)
            if h:
                self.hook_assign_heads.append((node.qual, h))
            return
        if not isinstance(value, ast.Call):
            return
        head = dotted_name(value.func)
        resolved = _expand_alias(node.aliases, head)
        lock_kind = {"threading.Lock": "Lock",
                     "threading.RLock": "RLock"}.get(resolved)
        traced = _is_tracer_head(head)
        clskey = self._class_for_ctor(node, head)
        # class-BODY statements are recorded on the enclosing module/
        # function node with ``cls`` set (node.cls != cls); a lock built
        # there (`_lock = threading.Lock()`) is still acquired via
        # ``self._lock``, so it must land in lock_attrs like the
        # __init__ form
        is_cls_body_name = (cls is not None and isinstance(tgt, ast.Name)
                            and node.cls != cls)
        if is_self_attr:
            key = (cls, tgt.attr)
            if lock_kind:
                self.lock_attrs[key] = lock_kind
            elif traced:
                self.attr_traced.add(key)
            elif clskey is not None:
                self.attr_types[key] = clskey
            else:
                self.attr_assign_calls[key] = head
        elif is_cls_body_name and lock_kind:
            self.lock_attrs[(cls, tgt.id)] = lock_kind
        elif isinstance(tgt, ast.Name):
            if traced:
                node.local_traced.add(tgt.id)
                if node.name == "<module>":
                    self.module_traced.add((node.rel, tgt.id))
            elif clskey is not None:
                node.local_types[tgt.id] = clskey
            else:
                node.local_assign_calls[tgt.id] = head
        # bind thread ctors to their assignment target (var or self attr)
        # so the DL103 join analysis can pair Thread() with .join() sites
        if resolved == "threading.Thread":
            bind = (tgt.attr if is_self_attr
                    else tgt.id if isinstance(tgt, ast.Name) else None)
            if bind:
                self._pending_thread_binds[(node.rel, value.lineno)] = bind

    def _class_for_ctor(self, node: FuncNode, head: str) -> Optional[tuple]:
        """(rel, clsname) when ``head`` is a constructor call of a project
        class — directly, via import alias, or dotted module path."""
        if "." not in head:
            ck = self.class_alias.get((node.rel, head))
            if ck is not None:
                return ck
            target = node.aliases.get(head)
            if target and "." in target:
                mod, _, name = target.rpartition(".")
                rel = self.module_of.get(mod)
                if rel is not None:
                    return self.class_alias.get((rel, name))
            return None
        mod, _, name = head.rpartition(".")
        mod = _expand_alias(node.aliases, mod)
        rel = self.module_of.get(mod)
        if rel is not None:
            return self.class_alias.get((rel, name))
        return None

    # -- resolution -----------------------------------------------------
    def _repo_tops(self) -> Set[str]:
        return {m.partition(".")[0] for m in self.module_of}

    def resolve(self, node: FuncNode, head: str) -> Tuple[tuple, bool]:
        """(target quals, dispatches_traced) for one dotted call head."""
        if not head:
            return ((), False)
        parts = head.split(".")
        if parts[0] == "":
            return ((), False)
        if len(parts) == 1:
            return self._resolve_bare(node, parts[0])
        if parts[0] in ("self", "cls") and node.cls is not None:
            out = self._resolve_typed(node.cls, parts[1:])
            if out is not None:
                return out
            return (self._fallback(parts[-1], node.rel), False)
        if parts[0] in node.local_types:
            out = self._resolve_typed(node.local_types[parts[0]], parts[1:])
            if out is not None:
                return out
            return (self._fallback(parts[-1], node.rel), False)
        # alias/module-dotted resolution
        target = node.aliases.get(parts[0])
        if target is not None:
            full = target + "." + ".".join(parts[1:])
            mod, _, fname = full.rpartition(".")
            rel = self.module_of.get(mod)
            if rel is not None:
                q = self.module_funcs.get((rel, fname))
                if q is not None:
                    return ((q,), q in self._jit_factories())
                ck = self.class_alias.get((rel, fname))
                if ck is not None:
                    init = self.classes.get(ck, {}).get("__init__")
                    return ((init,) if init else (), False)
            if full.partition(".")[0] not in self._repo_tops():
                return ((), False)   # external library: no fallback
        return (self._fallback(parts[-1], node.rel), False)

    def _resolve_bare(self, node: FuncNode, name: str) -> Tuple[tuple, bool]:
        cur = node
        while cur is not None:          # closures see enclosing defs
            if name in cur.children:
                return ((cur.children[name],), False)
            if name in cur.local_traced:
                return ((), True)
            ah = cur.local_assign_calls.get(name)
            if ah is not None:
                key = (id(cur), ah)
                if key not in self._resolving:
                    self._resolving.add(key)
                    try:
                        targets, _ = self.resolve(cur, ah)
                    finally:
                        self._resolving.discard(key)
                    if any(t in self._jit_factories() for t in targets):
                        return ((), True)   # var = make_step(...) -> traced
            cur = cur.parent
        q = self.module_funcs.get((node.rel, name))
        if q is not None:
            return ((q,), q in self._jit_factories())
        if (node.rel, name) in self.module_traced:
            return ((), True)
        target = node.aliases.get(name)
        if target is not None:
            if "." in target:
                mod, _, fname = target.rpartition(".")
                rel = self.module_of.get(mod)
                if rel is not None:
                    q = self.module_funcs.get((rel, fname))
                    if q is not None:
                        return ((q,), q in self._jit_factories())
                    ck = self.class_alias.get((rel, fname))
                    if ck is not None:
                        init = self.classes.get(ck, {}).get("__init__")
                        return ((init,) if init else (), False)
        return ((), False)

    def _resolve_typed(self, clskey: tuple,
                       parts: Sequence[str]) -> Optional[Tuple[tuple, bool]]:
        cur = clskey
        for a in parts[:-1]:
            nxt = self.attr_types.get((cur, a))
            if nxt is None:
                ah = self.attr_assign_calls.get((cur, a))
                if ah is not None:
                    # one-hop return-type inference: factory returning a
                    # direct constructor call (serve_metrics -> MetricsServer)
                    for q in self._heads_to_quals(cur, ah):
                        rc = self.funcs[q].return_class
                        if rc is not None:
                            ck = self.class_alias.get((self.funcs[q].rel, rc))
                            if ck is not None:
                                nxt = ck
                                break
            if nxt is None:
                return None
            cur = nxt
        m = parts[-1]
        q = self.classes.get(cur, {}).get(m)
        if q is not None:
            return ((q,), False)
        if (cur, m) in self.attr_traced:
            return ((), True)
        ah = self.attr_assign_calls.get((cur, m))
        if ah is not None:
            # self.train_step = compile_train_step(...): traced handle when
            # the callee is (transitively) a jit factory
            owner_rel = cur[0]
            mod_node = self.funcs.get(f"{owner_rel}::<module>")
            base = mod_node if mod_node is not None else None
            if base is not None:
                targets, traced = self.resolve(base, ah)
                if traced or any(t in self._jit_factories()
                                 for t in targets):
                    return ((), True)
        return None

    def _heads_to_quals(self, clskey, head) -> tuple:
        rel = clskey[0]
        mod_node = self.funcs.get(f"{rel}::<module>")
        if mod_node is None:
            return ()
        targets, _ = self.resolve(mod_node, head)
        return targets

    def _fallback(self, name: str, from_rel: Optional[str] = None) -> tuple:
        if name in _FALLBACK_NOISE or name.startswith("__"):
            return ()
        out = self.methods_by_name.get(name, ())
        # deterministic under overlays: only the overlay file itself may
        # fallback-resolve into its own methods
        return tuple(q for q in out
                     if (rel := q.partition("::")[0]) == from_rel
                     or rel not in self.overlay_files)

    # -- derived sets (memoized per version) ----------------------------
    def _memoized(self, key: str, compute):
        hit = self._memo.get(key)
        if hit is not None and hit[0] == self._version:
            return hit[1]
        val = compute()
        self._memo[key] = (self._version, val)
        return val

    def _jit_factories(self) -> Set[str]:
        def compute():
            # fixpoint WITHOUT resolve() (resolve consults this set):
            # direct `return jit(...)` seeds, then one name-resolution
            # round per iteration for `return make_inner(...)` chains
            fac = {q for q, n in self.funcs.items() if n.returns_jit}
            changed = True
            while changed:
                changed = False
                for q, n in self.funcs.items():
                    if q in fac:
                        continue
                    for rc in n.return_calls:
                        if "." in rc:
                            continue
                        tq = self.module_funcs.get((n.rel, rc))
                        if tq is None and n.parent is not None:
                            tq = n.parent.children.get(rc)
                        if tq is None:
                            # cross-module factory chain through an import
                            # alias (`from plan.compile import
                            # compile_train_step` in a function that
                            # returns its product): the chain must survive
                            # the module boundary — a plain table lookup,
                            # no resolve() recursion
                            target = n.aliases.get(rc)
                            if target and "." in target:
                                mod, _, fname = target.rpartition(".")
                                rel = self.module_of.get(mod)
                                if rel is not None:
                                    tq = self.module_funcs.get((rel, fname))
                        if tq in fac:
                            fac.add(q)
                            changed = True
                            break
            return fac
        return self._memoized("jit_factories", compute)

    def traced_funcs(self) -> Set[str]:
        """Functions whose BODY is jit/shard_map-traced: decorated, passed
        to jit(f), or defined inside a jit factory (the step closures)."""
        def compute():
            out = set(self.decorated_traced)
            for qual, name in self.jit_mark_heads:
                n = self.funcs.get(qual)
                if n is not None:
                    targets, _ = self._resolve_bare(n, name)
                    out.update(targets)
            for fq in self._jit_factories():
                n = self.funcs.get(fq)
                if n is not None:
                    out.update(n.children.values())
            return out
        return self._memoized("traced", compute)

    def edges(self, qual: str) -> Tuple[tuple, bool]:
        """(resolved same-scope callee quals, dispatches_traced)."""
        hit = self._edges.get(qual)
        if hit is not None:
            return hit
        n = self.funcs.get(qual)
        if n is None:
            return ((), False)
        targets: List[str] = []
        traced = False
        for head, _ in n.calls:
            t, tr = self.resolve(n, head)
            targets.extend(t)
            traced = traced or tr
        out = (tuple(dict.fromkeys(targets)), traced)
        self._edges[qual] = out
        return out

    def reachable_from(self, roots: Iterable[str]) -> Set[str]:
        """Forward closure over call edges (cycle-tolerant BFS)."""
        seen: Set[str] = set()
        stack = [r for r in roots if r in self.funcs]
        while stack:
            q = stack.pop()
            if q in seen:
                continue
            seen.add(q)
            for t in self.edges(q)[0]:
                if t not in seen:
                    stack.append(t)
        return seen

    def _heads_set(self, pairs: List[Tuple[str, str]]) -> Set[str]:
        out: Set[str] = set()
        for qual, head in pairs:
            n = self.funcs.get(qual)
            if n is None:
                continue
            targets, _ = self.resolve(n, head)
            out.update(targets)
        return out

    def signal_handlers(self) -> Set[str]:
        return self._memoized(
            "sig", lambda: self._heads_set(self.signal_handler_heads))

    def atexit_hooks(self) -> Set[str]:
        return self._memoized(
            "atexit", lambda: self._heads_set(self.atexit_heads))

    def hook_assigns(self) -> Set[str]:
        return self._memoized(
            "hooks", lambda: self._heads_set(self.hook_assign_heads))

    def thread_targets(self) -> Set[str]:
        def compute():
            pairs = []
            for rel, recs in self.thread_ctors.items():
                for r in recs:
                    if r["target_head"]:
                        pairs.append((r["qual"], r["target_head"]))
            return self._heads_set(pairs)
        return self._memoized("threads", compute)

    def escaped_callbacks(self) -> Set[str]:
        """Functions whose references escape through call arguments (sink
        registrations etc.) plus closures returned by factories whose
        results are passed along — conservatively callable from the main
        line of execution."""
        def compute():
            out: Set[str] = set()
            for n in self.funcs.values():
                for h in n.arg_refs:
                    targets, _ = self.resolve(n, h)
                    out.update(targets)
                for h in n.factory_args:
                    targets, _ = self.resolve(n, h)
                    for t in targets:
                        tn = self.funcs.get(t)
                        if tn is not None:
                            out.update(tn.children.values())
            return out
        return self._memoized("escaped", compute)

    def handler_reachable(self) -> Set[str]:
        return self._memoized(
            "hreach", lambda: self.reachable_from(self.signal_handlers()))

    def mainline_reachable(self) -> Set[str]:
        """Reachable from non-signal entry points: module-level code,
        thread targets, atexit/excepthook hooks, and escaped callbacks."""
        def compute():
            roots = {q for q in self.funcs if q.endswith("::<module>")}
            roots |= self.thread_targets() | self.atexit_hooks()
            roots |= self.hook_assigns() | self.escaped_callbacks()
            return self.reachable_from(roots)
        return self._memoized("mreach", compute)

    def shutdown_reachable(self) -> Set[str]:
        """Reachable from the run-teardown surface (DL103's join check):
        atexit hooks, signal handlers, excepthooks, and methods
        conventionally on the shutdown path."""
        def compute():
            roots = (self.atexit_hooks() | self.signal_handlers()
                     | self.hook_assigns())
            for q, n in self.funcs.items():
                if n.name in ("close", "stop", "shutdown", "run_end",
                              "__exit__", "__del__"):
                    roots.add(q)
            return self.reachable_from(roots)
        return self._memoized("shutdown", compute)

    def file_nodes(self, rel: str) -> List[FuncNode]:
        """The FuncNodes of one indexed file (module pseudo-node first)."""
        return [self.funcs[q] for q in self.file_quals.get(rel, ())
                if q in self.funcs]

    def reaches_traced(self) -> Set[str]:
        """Functions from which a traced (jit) dispatch is reachable —
        the 'this code drives the device' closure DL002 derives hot loops
        from."""
        def compute():
            rev: Dict[str, List[str]] = {}
            seeds: List[str] = []
            for q in self.funcs:
                targets, traced = self.edges(q)
                if traced:
                    seeds.append(q)
                for t in targets:
                    rev.setdefault(t, []).append(q)
            seen: Set[str] = set()
            stack = list(seeds)
            while stack:
                q = stack.pop()
                if q in seen:
                    continue
                seen.add(q)
                stack.extend(rev.get(q, ()))
            return seen
        return self._memoized("reaches_traced", compute)


class graph_scope:
    """Context manager giving a rule the project graph WITH the current
    file indexed. Out-of-surface files (fixtures, tmp snippets) are
    removed again on exit so one test's deliberately-bad code never
    leaks roots into another's reachability queries."""

    def __init__(self, project: Project, ctx: "FileContext"):
        self._graph = project.callgraph
        self._ctx = ctx
        self._added = False

    def __enter__(self) -> CallGraph:
        self._added = self._graph.ensure_file(self._ctx.rel,
                                              tree=self._ctx.tree,
                                              path=self._ctx.path,
                                              src=self._ctx.src)
        return self._graph

    def __exit__(self, *exc) -> None:
        if self._added:
            self._graph.remove_file(self._ctx.rel)


_GRAPH_CACHE: Dict[str, CallGraph] = {}


def load_callgraph(root: str = REPO_ROOT) -> CallGraph:
    """Process-wide cached call graph over :data:`GRAPH_SURFACE` (the
    build parses every surface file once; ~100ms-scale, amortized across
    every rule and every test in the process)."""
    root = os.path.abspath(root)
    g = _GRAPH_CACHE.get(root)
    if g is None:
        g = CallGraph(root)
        present = [p for p in GRAPH_SURFACE
                   if os.path.exists(os.path.join(root, p))]
        if present:
            for path in iter_python_files(present, root):
                rel = os.path.relpath(path, root).replace(os.sep, "/")
                g.ensure_file(rel, path=path)
        g._base_built = True   # everything added from here on is overlay
        _GRAPH_CACHE[root] = g
    return g


# ----------------------------------------------------------- ast helpers
def dotted_name(node: ast.AST) -> str:
    """Dotted receiver chain: ``jax.random.split`` -> 'jax.random.split',
    ``self.obs.ledger`` -> 'self.obs.ledger'. Non-name roots (calls,
    subscripts) contribute an empty head: ``foo().bar`` -> '.bar'."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    parts.append(node.id if isinstance(node, ast.Name) else "")
    return ".".join(reversed(parts))


def terminal_name(node: ast.AST) -> str:
    """The final component of a name/attribute chain ('' otherwise)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""
