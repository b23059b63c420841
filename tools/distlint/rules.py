"""The distlint rule set: SPMD-correctness hazards visible in source.

Every rule is a pure function of (FileContext, Project) returning
:class:`~tools.distlint.core.Finding` objects. The hazards are the failure
classes the PR 2 watchdog can only report AFTER they hang a pod at runtime;
GSPMD single-program multi-host JAX makes them statically visible:

DL001  collectives/checkpoints reachable only under host-divergent guards
       (``process_index() == 0``-style) — the other hosts never enter the
       collective and the pod deadlocks.
DL002  blocking host syncs inside the engines' hot step loops — each one
       drains the async-dispatch queue and serializes the device.
DL003  axis-name literals in PartitionSpec/collective calls validated
       against the mesh axes declared in tpu_dist/parallel/mesh.py —
       a typo'd axis only explodes at trace time, on hardware.
DL004  untraced Python side effects (print/time.time/ledger emits) inside
       jit/pjit/shard_map-traced functions — they fire once at trace time,
       then never again, which is a lie in a log.
DL005  PRNG hygiene: a key consumed twice (correlated draws), and global
       numpy/stdlib RNG state (per-process divergence, irreproducibility).
DL006  every ``*ledger*.emit(...)`` call site conforms to EVENT_SCHEMA
       (the absorbed tools/check_ledger_schema check).
DL007  buffers donated to a jitted call (``donate_argnums``) referenced
       afterwards — the device buffer may already be reused by XLA.
DL008  bare ``jax.device_put`` on the hot step path outside the loader /
       prefetcher — the copy dispatch belongs on the producer thread
       (data.loader.DevicePrefetcher), not the step loop.  [warn tier]

The DL1xx family rides the cross-file call graph + reachability pass
(core.CallGraph): concurrency and signal-safety hazards in the threaded
obs layer, the failure class PR 5's Ledger SIGTERM deadlock proved real:

DL101  non-reentrant ``threading.Lock`` acquired on a path reachable from
       a signal handler while the same lock guards main-thread emit
       sites (the exact PR-5 self-deadlock; the shipped RLock is clean).
DL102  blocking I/O (subprocess/socket/HTTP/sleep) while holding a lock
       the hot-path emit fan-out also takes.  [warn tier]
DL103  ``threading.Thread`` without ``daemon=True`` and without a join on
       the shutdown path — a crashed run that never exits.  [warn tier]
DL104  signal handlers calling non-reentrant stdlib (logging, io flush
       chains), and ``signal.signal`` installs that drop the previously
       installed handler instead of chaining it.

Severity tiers: every rule carries ``severity`` ('error' gates CI via
scripts/lint.sh; 'warn' reports without failing the build).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.distlint.core import (FileContext, Finding, Project, dotted_name,
                                 graph_scope, terminal_name)


class Rule:
    id = "DL999"
    title = ""
    rationale = ""
    # severity tier: 'error' findings gate CI (scripts/lint.sh exits
    # non-zero); 'warn' findings report but do not fail the build — the
    # tier for heuristic-leaning rules whose false-positive cost is real
    severity = "error"
    # graph-backed rules open graph_scope; lint_files hoists ONE
    # ensure/remove of the file per lint pass when any is selected, so
    # five rules don't re-index (and re-invalidate the reachability
    # memos of) an out-of-surface file five times
    uses_graph = False

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        raise NotImplementedError

    def finding(self, ctx: FileContext, node: ast.AST, message: str) -> Finding:
        return Finding(self.id, ctx.rel, getattr(node, "lineno", 0),
                       getattr(node, "col_offset", 0), message)


def _assign_parts(stmt: ast.AST) -> Tuple[Optional[ast.AST],
                                          Optional[ast.AST]]:
    """(target, value) for plain and annotated single-target assigns."""
    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
        return stmt.targets[0], stmt.value
    if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
        return stmt.target, stmt.value
    return None, None


def _calls(node: ast.AST) -> Iterable[ast.Call]:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _calls_same_scope(node: ast.AST) -> Iterable[ast.Call]:
    """Calls that EXECUTE when ``node`` executes: nested function/lambda
    bodies are pruned (they run at call time, not definition time)."""
    stack = list(ast.iter_child_nodes(node))
    if isinstance(node, ast.Call):
        yield node
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _block_exits(stmts: Sequence[ast.stmt]) -> bool:
    """Does this block unconditionally leave the enclosing code path?"""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


# ------------------------------------------------------------------ DL001
class HostDivergentCollectives(Rule):
    id = "DL001"
    title = "collective under host-divergent guard"
    rationale = ("a collective (or collective-entering call like "
                 "save_checkpoint/assemble_global) that only a subset of "
                 "processes reaches deadlocks the pod: the others wait in "
                 "the next collective forever")

    # call names that enter a cross-process collective (directly or, like
    # save_checkpoint's sharded gather, conditionally inside)
    COLLECTIVES = {
        "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
        "ppermute", "pshuffle", "axis_index", "psum_scatter",
        "process_allgather", "sync_global_devices", "broadcast_one_to_all",
        "assemble_global", "make_array_from_process_local_data",
        "save_checkpoint", "barrier", "allreduce", "adasum_reduce",
        # the ring/decode collectives (parallel/overlap.py,
        # parallel/collectives.py): ppermute/psum_scatter chains under the
        # hood, so a host-divergent guard around them deadlocks identically
        "ring_allreduce", "ring_allgather_matmul",
        "ring_matmul_reduce_scatter", "bucketed_grad_sync", "reduce_mean",
    }
    _DIVERGENT_NAMES = {"is_main", "is_master", "is_primary", "main_process"}
    _GATE_RE = re.compile(r"process_index|is_main|is_master|is_primary|"
                          r"main_process|rank")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if not self._GATE_RE.search(ctx.src):
            return []   # no divergence vocabulary: no guard to flag
        out: List[Finding] = []
        self._scan(ctx.tree.body, False, ctx, out)
        return out

    def _divergent(self, test: ast.AST) -> bool:
        for n in ast.walk(test):
            if (isinstance(n, ast.Call)
                    and terminal_name(n.func) == "process_index"):
                return True
            if (isinstance(n, (ast.Name, ast.Attribute))
                    and terminal_name(n) in self._DIVERGENT_NAMES):
                return True
            if isinstance(n, ast.Compare):
                # bare `rank` names only: `t.rank == 2` is a tensor-rank
                # check, identical on every host, not a process guard
                bare = {x.id for x in ast.walk(n) if isinstance(x, ast.Name)}
                attrs = {terminal_name(x) for x in ast.walk(n)
                         if isinstance(x, ast.Attribute)}
                if "rank" in bare or "process_index" in bare | attrs:
                    return True
        return False

    def _flag_collectives(self, node: ast.AST, ctx: FileContext,
                          out: List[Finding], how: str) -> None:
        # same-scope only: a function merely DEFINED under the guard may be
        # called on every host — flagging its body would be a false alarm
        for call in _calls_same_scope(node):
            name = terminal_name(call.func)
            if name in self.COLLECTIVES:
                out.append(self.finding(
                    ctx, call,
                    f"collective call '{name}' is reachable only on a "
                    f"subset of processes ({how}); the excluded hosts "
                    "never enter it and the pod deadlocks at the next "
                    "collective"))

    def _scan(self, stmts: Sequence[ast.stmt], active: bool,
              ctx: FileContext, out: List[Finding]) -> bool:
        """Linear pass with an 'active' flag: after an early return taken
        only on some processes, the REST of the block is host-divergent."""
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                # new runtime scope: divergence does not leak into a body
                # that executes at call time, not definition time
                body = s.body
                self._scan(body, False, ctx, out)
                continue
            if active:
                self._flag_collectives(s, ctx, out,
                                       "code after a process_index-guarded "
                                       "early return")
                continue
            if isinstance(s, ast.If) and self._divergent(s.test):
                self._flag_collectives(
                    s, ctx, out, "inside a process_index/is_main guard")
                # 'if not main: return' makes everything AFTER main-only;
                # symmetric for a guarded else-branch exit
                if _block_exits(s.body) or (s.orelse
                                            and _block_exits(s.orelse)):
                    active = True
                continue
            # sub-blocks are scanned with the INCOMING flag (an If's orelse
            # must not inherit divergence its sibling body introduced), but
            # a guarded early return inside ANY of them makes the code
            # after this statement divergent — propagate by OR-ing after
            escaped = False
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(s, attr, None)
                if sub:
                    escaped = self._scan(sub, active, ctx, out) or escaped
            for h in getattr(s, "handlers", ()):
                escaped = self._scan(h.body, active, ctx, out) or escaped
            active = active or escaped
        return active


# ------------------------------------------------------------------ DL002
class HotLoopHostSync(Rule):
    uses_graph = True
    id = "DL002"
    title = "blocking host sync on the hot step path"
    rationale = ("each .item()/device_get/np.asarray inside the step loop "
                 "drains the async-dispatch queue, serializing host and "
                 "device — the exact failure the drain-boundary design "
                 "avoids")

    # What counts as hot is DERIVED, not listed: a loop is a step loop
    # when its body (transitively, through the call graph) dispatches a
    # jit/shard_map-traced computation — either a resolved traced handle
    # (self.train_step = compile_train_step(...) whose chain of returns
    # ends in jax.jit(...)) or, as a syntactic backstop, a callee whose name says
    # it dispatches steps. Everything REACHABLE from a step-loop body is
    # hot too, which closes the old closure seam: a .item() inside a
    # helper or nested def that the loop calls no longer escapes because
    # the def's body sat outside the loop's lexical extent.
    STEP_NAME_RE = re.compile(r"step|dispatch", re.I)
    BLOCKING_METHODS = {"item", "block_until_ready", "tolist"}
    BLOCKING_QUALS = {"jax.device_get", "device_get", "numpy.asarray",
                      "numpy.array", "jax.block_until_ready"}
    # the reachable-body scan (helpers called FROM a hot loop) narrows
    # only the QUALS: np.asarray/float(x) on host values is ordinary
    # Python in a constructor or parser, and flagging it there would
    # bury the real syncs in noise — lexically inside a step loop the
    # odds flip, so the full qual set applies only there. The method
    # set (.item()/.tolist()/.block_until_ready()) is unambiguous in
    # either position and applies to both tiers.
    STRICT_QUALS = {"jax.device_get", "device_get",
                    "jax.block_until_ready"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            reaches = g.reaches_traced()
            traced = g.traced_funcs()
            hot = self._hot_funcs(g, reaches, traced)
            for node in g.file_nodes(ctx.rel):
                if node.qual in traced:
                    continue
                # lexical: statements inside this file's hot loop bodies —
                # the `<module>` pseudo-node included (a top-level step
                # loop in a script is as hot as one in a function; only
                # the hot-BODY rescan below needs a real def node).
                # Loops with GRAPH EVIDENCE of a traced dispatch get the
                # full blocking set (float(x)/np.asarray included); loops
                # hot only by callee NAME get the strict set — a drain
                # loop iterating already-fetched host floats must not
                # drown the report in int(host_value) noise
                for loop in node.loops:
                    how = self._loop_is_hot(node, loop, g, reaches, traced)
                    if how:
                        for stmt in loop.body + loop.orelse:
                            self._scan_stmt(stmt, node.name, ctx, out,
                                            strict=(how == 1),
                                            lexical=True)
                # reachability: whole body of functions called (directly
                # or transitively) from ANY hot loop body in the project
                if node.node is not None and node.qual in hot:
                    for stmt in node.node.body:
                        self._scan_stmt(stmt, node.name, ctx, out,
                                        strict=True, lexical=False)
        seen: set = set()
        uniq: List[Finding] = []
        for f in sorted(out, key=lambda f: (f.line, f.col)):
            if (f.line, f.col) not in seen:
                seen.add((f.line, f.col))
                uniq.append(f)
        return uniq

    def _loop_calls(self, node, loop) -> List[str]:
        """Same-scope call heads whose call site sits inside ``loop``'s
        body (the node's call list excludes nested-def bodies already)."""
        end = getattr(loop, "end_lineno", loop.lineno)
        return [h for h, line in node.calls if loop.lineno <= line <= end]

    def _loop_is_hot(self, node, loop, g, reaches, traced) -> int:
        """0 = not hot; 2 = hot with graph evidence (a body call resolves
        to a traced computation); 1 = hot by callee name only."""
        how = 0
        for head in self._loop_calls(node, loop):
            targets, is_traced = g.resolve(node, head)
            if is_traced or any(t in reaches or t in traced
                                for t in targets):
                return 2
            if self.STEP_NAME_RE.search(head.rpartition(".")[2]):
                how = 1
        return how

    def _hot_funcs(self, g, reaches, traced) -> set:
        """Functions reachable from any hot loop body in the graph (the
        project surface plus the file under lint), minus traced bodies —
        memoized on the graph version."""
        def compute():
            roots: List[str] = []
            for node in g.funcs.values():
                if node.qual in traced:
                    continue   # module nodes seed too: top-level loops
                for loop in node.loops:
                    if self._loop_is_hot(node, loop, g, reaches, traced):
                        for head in self._loop_calls(node, loop):
                            targets, _ = g.resolve(node, head)
                            roots.extend(t for t in targets
                                         if t not in traced)
            return g.reachable_from(roots) - traced
        return g._memoized("dl002_hot", compute)

    def _scan_stmt(self, stmt: ast.stmt, fn_name: str, ctx: FileContext,
                   out: List[Finding], strict: bool = False,
                   lexical: bool = True) -> None:
        # `strict` narrows the blocking-qual set; `lexical` picks the
        # message (inside this loop vs reachable from one) — independent
        # axes: a name-only hot loop is strict AND lexical
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # separate node: the reachability pass covers it
        for child in ast.iter_child_nodes(stmt):
            self._scan_stmt(child, fn_name, ctx, out, strict, lexical)
        if isinstance(stmt, ast.Call):
            n = stmt
            bad = None
            tname = terminal_name(n.func)
            qual = ctx.resolve(dotted_name(n.func))
            methods = self.BLOCKING_METHODS   # same set in both tiers
            quals = self.STRICT_QUALS if strict else self.BLOCKING_QUALS
            if isinstance(n.func, ast.Attribute) and tname in methods:
                bad = f".{tname}()"
            elif qual in quals:
                bad = qual
            elif (not strict and isinstance(n.func, ast.Name)
                  and n.func.id in ("float", "int") and n.args
                  and isinstance(n.args[0], (ast.Name, ast.Attribute))):
                # float(x)/int(x) on a bare name is the classic implicit
                # device->host sync; subscript/call args are usually reads
                # of an already-fetched dict and stay silent
                bad = f"{n.func.id}({dotted_name(n.args[0])})"
            if bad:
                where = (f"inside the hot loop of {fn_name}()" if lexical
                         else f"in {fn_name}(), reachable from a hot "
                              f"step loop")
                out.append(self.finding(
                    ctx, n,
                    f"blocking host sync {bad!r} {where} stalls async "
                    "dispatch; queue device values and fetch them at a "
                    "drain boundary instead"))


# ------------------------------------------------------------------ DL003
class UnknownMeshAxis(Rule):
    id = "DL003"
    title = "axis name not declared on the mesh"
    rationale = ("a typo'd PartitionSpec axis ('modle') passes every CPU "
                 "test and only explodes at trace time on the pod; the "
                 "declared axes in parallel/mesh.py are the authority")

    SPEC_CTORS = {"P", "PartitionSpec"}
    AXIS_ARG_CALLS = {"psum", "pmean", "pmax", "pmin", "all_gather",
                      "all_to_all", "ppermute", "axis_index", "pbroadcast",
                      "psum_scatter", "axis_size"}

    _GATE_RE = re.compile(r"PartitionSpec|P\(|psum|pmean|pmax|pmin|"
                          r"all_gather|all_to_all|ppermute|axis_index|"
                          r"pbroadcast|axis_size|\.shape\[")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        axes = project.mesh_axes
        if not axes or not self._GATE_RE.search(ctx.src):
            return []
        out: List[Finding] = []
        for call in _calls(ctx.tree):
            tname = terminal_name(call.func)
            if tname in self.SPEC_CTORS:
                for lit in self._axis_literals(list(call.args)
                                               + [k.value for k in
                                                  call.keywords]):
                    self._validate(lit, axes, ctx, out, "PartitionSpec")
            elif tname in self.AXIS_ARG_CALLS:
                # axis_index/axis_size(axis_name) take the axis FIRST;
                # the psum family takes (value, axis_name)
                pos = 0 if tname in ("axis_index", "axis_size") else 1
                cands = list(call.args[pos:pos + 1]) + [
                    k.value for k in call.keywords
                    if k.arg in ("axis_name", "axis")]
                for lit in self._axis_literals(cands):
                    self._validate(lit, axes, ctx, out, f"{tname}()")
        # mesh.shape["axis"] — Mesh.shape is keyed by axis NAME; a typo'd
        # key raises KeyError only when the serving path first sizes the
        # axis on hardware (array .shape subscripts are ints, never str)
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "shape"
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                self._validate(node.slice, axes, ctx, out,
                               f"{dotted_name(node.value)}[...]")
        return out

    def _axis_literals(self, nodes) -> Iterable[ast.Constant]:
        for n in nodes:
            if isinstance(n, (ast.Tuple, ast.List)):
                yield from self._axis_literals(n.elts)
            elif isinstance(n, ast.Constant) and isinstance(n.value, str):
                yield n

    def _validate(self, lit: ast.Constant, axes: Set[str], ctx: FileContext,
                  out: List[Finding], where: str) -> None:
        if lit.value not in axes:
            out.append(self.finding(
                ctx, lit,
                f"axis {lit.value!r} in {where} is not a mesh axis "
                f"declared in tpu_dist/parallel/mesh.py "
                f"({sorted(axes)}); a typo here fails only at trace "
                "time on hardware"))


# ------------------------------------------------------------------ DL004
class TracedSideEffect(Rule):
    id = "DL004"
    title = "untraced Python side effect in jitted code"
    rationale = ("print/time.time/ledger emits inside jit/shard_map bodies "
                 "run ONCE at trace time and never again — a stale lie in "
                 "the logs; use jax.debug.print/callback or hoist to the "
                 "host loop")

    SIDE_EFFECT_QUALS = {"time.time", "time.perf_counter", "time.monotonic",
                         "time.sleep", "builtins.print"}
    SIDE_EFFECT_NAMES = {"print", "input", "breakpoint"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "jit" not in ctx.src and "shard_map" not in ctx.src:
            return []   # nothing traced here
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for n in ast.walk(ctx.tree):
            if isinstance(n, ast.FunctionDef):
                defs.setdefault(n.name, []).append(n)
        traced: List[ast.FunctionDef] = []
        seen: Set[int] = set()

        def mark(name: str) -> None:
            for fn in defs.get(name, ()):
                if id(fn) not in seen:
                    seen.add(id(fn))
                    traced.append(fn)

        def mark_nested(name: str) -> None:
            """jit(factory(...)): the TRACED code is whatever the factory
            returns — its nested defs — while the factory's own body is
            host-side build code that runs once and may print/time freely."""
            for fn in defs.get(name, ()):
                for n in ast.walk(fn):
                    if isinstance(n, ast.FunctionDef) and n is not fn \
                            and id(n) not in seen:
                        seen.add(id(n))
                        traced.append(n)

        for fn_list in defs.values():
            for fn in fn_list:
                if any(self._is_tracer(d, ctx) for d in fn.decorator_list):
                    mark(fn.name)
        for call in _calls(ctx.tree):
            if not self._is_tracer_call(call, ctx) or not call.args:
                continue
            arg = call.args[0]
            if isinstance(arg, ast.Name):
                mark(arg.id)
            elif isinstance(arg, ast.Call):
                inner = arg
                if terminal_name(inner.func) == "partial" and inner.args \
                        and isinstance(inner.args[0], ast.Name):
                    mark(inner.args[0].id)       # jit(partial(f, ...))
                else:
                    # factory pattern: jit(make_step(...)) traces the
                    # function the factory RETURNS — its nested defs
                    mark_nested(terminal_name(inner.func))
        out: List[Finding] = []
        for fn in traced:
            self._scan(fn, ctx, out)
        return out

    def _is_tracer(self, node: ast.AST, ctx: FileContext) -> bool:
        """jit / pjit / *shard_map* as a name, attribute, partial(...) or
        configured-call decorator."""
        if isinstance(node, ast.Call):
            if terminal_name(node.func) == "partial":
                return any(self._is_tracer(a, ctx) for a in node.args[:1])
            return self._is_tracer(node.func, ctx)
        t = terminal_name(node)
        return t in ("jit", "pjit") or "shard_map" in t

    def _is_tracer_call(self, call: ast.Call, ctx: FileContext) -> bool:
        t = terminal_name(call.func)
        return t in ("jit", "pjit") or "shard_map" in t

    def _scan(self, fn: ast.FunctionDef, ctx: FileContext,
              out: List[Finding]) -> None:
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            qual = ctx.resolve(dotted_name(n.func))
            if "debug" in qual or "callback" in qual:
                continue  # jax.debug.print / io_callback: the traced-safe way
            tname = terminal_name(n.func)
            hit = None
            if isinstance(n.func, ast.Name) \
                    and n.func.id in self.SIDE_EFFECT_NAMES:
                hit = n.func.id
            elif qual in self.SIDE_EFFECT_QUALS:
                hit = qual
            elif tname == "emit" and _is_ledger_receiver(n.func):
                hit = f"{dotted_name(n.func)}()"
            if hit:
                out.append(self.finding(
                    ctx, n,
                    f"untraced side effect {hit!r} inside the traced "
                    f"function {fn.name}() runs once at trace time and "
                    "never per step; use jax.debug.print/io_callback or "
                    "hoist it to the host loop"))


# ------------------------------------------------------------------ DL005
class PrngHygiene(Rule):
    id = "DL005"
    title = "PRNG key reuse / global RNG state"
    rationale = ("a key consumed twice yields correlated draws (silently "
                 "wrong statistics); global numpy/stdlib RNG state "
                 "diverges across processes and kills reproducibility — "
                 "use seeded np.random.default_rng / jax.random.fold_in")

    CONSUMERS = {"split", "normal", "uniform", "randint", "bernoulli",
                 "categorical", "permutation", "choice", "bits", "gamma",
                 "beta", "gumbel", "exponential", "laplace", "poisson",
                 "truncated_normal", "rademacher", "orthogonal", "shuffle",
                 "randint_like", "loggamma", "dirichlet", "multivariate_normal"}
    NP_SAFE = {"default_rng", "RandomState", "Generator", "SeedSequence",
               "get_state", "set_state", "bit_generator"}
    STDLIB_SAFE = {"Random", "SystemRandom"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "random" not in ctx.src:
            return []   # both halves of the rule need RNG vocabulary
        out: List[Finding] = []
        for n in ast.walk(ctx.tree):
            if isinstance(n, ast.Call):
                self._check_global_rng(n, ctx, out)
        for n in ast.walk(ctx.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_key_reuse(n, ctx, out)
        return out

    # -- global RNG state ------------------------------------------------
    def _check_global_rng(self, call: ast.Call, ctx: FileContext,
                          out: List[Finding]) -> None:
        qual = ctx.resolve(dotted_name(call.func))
        parts = qual.split(".")
        if len(parts) >= 3 and parts[-3] == "numpy" and parts[-2] == "random":
            if parts[-1] not in self.NP_SAFE:
                out.append(self.finding(
                    ctx, call,
                    f"global numpy RNG call '{qual}' draws from hidden "
                    "per-process state (seeding races, host divergence); "
                    "use a seeded np.random.default_rng(seed) generator"))
        elif len(parts) == 2 and parts[0] == "random":
            # qual is RESOLVED through the import table, so `import random
            # as rnd; rnd.randint` and `from random import randint` both
            # land here; `from jax import random` resolves to jax.random.*
            # (3 parts) and never does
            if parts[-1] not in self.STDLIB_SAFE:
                out.append(self.finding(
                    ctx, call,
                    f"stdlib global RNG call '{qual}' is process-local "
                    "hidden state; use random.Random(seed) or jax.random"))

    # -- jax key reuse ---------------------------------------------------
    def _check_key_reuse(self, fn: ast.AST, ctx: FileContext,
                         out: List[Finding]) -> None:
        uses: Dict[str, List[Tuple[int, ast.Call, tuple]]] = {}
        assigns: Dict[str, List[int]] = {}
        branches: Dict[int, tuple] = {}
        scope_nodes: List[ast.AST] = []

        def walk(node: ast.AST, path: tuple) -> None:
            for child_name, value in ast.iter_fields(node):
                kids = value if isinstance(value, list) else [value]
                for kid in kids:
                    if not isinstance(kid, ast.AST):
                        continue
                    sub = path
                    if isinstance(node, (ast.If, ast.Try)) \
                            and child_name in ("body", "orelse", "handlers",
                                               "finalbody"):
                        sub = path + ((id(node), child_name),)
                    if isinstance(kid, (ast.FunctionDef,
                                        ast.AsyncFunctionDef, ast.Lambda)) \
                            and kid is not fn:
                        continue   # nested scopes analyzed on their own
                    branches[id(kid)] = sub
                    scope_nodes.append(kid)
                    walk(kid, sub)

        branches[id(fn)] = ()
        walk(fn, ())

        for n in scope_nodes:
            if isinstance(n, ast.Call):
                qual = ctx.resolve(dotted_name(n.func))
                parts = qual.split(".")
                is_jax_rng = (len(parts) >= 3 and parts[-2] == "random"
                              and parts[-3] not in ("numpy",)
                              and parts[-1] in self.CONSUMERS)
                if is_jax_rng and n.args \
                        and isinstance(n.args[0], ast.Name):
                    uses.setdefault(n.args[0].id, []).append(
                        (n.lineno, n, branches.get(id(n), ())))
            for tgt in self._assign_targets(n):
                lineno = getattr(n, "lineno", None) or getattr(
                    getattr(n, "optional_vars", None), "lineno", 0)
                assigns.setdefault(tgt, []).append(lineno)

        for name, consumptions in uses.items():
            consumptions.sort(key=lambda u: u[0])
            for (l1, _, b1), (l2, node2, b2) in zip(consumptions,
                                                    consumptions[1:]):
                if any(l1 <= a < l2 for a in assigns.get(name, ())):
                    continue   # rebound between the two uses (rng, sub = ...)
                if self._sibling_branches(b1, b2):
                    continue   # if/else arms: only one executes
                out.append(self.finding(
                    ctx, node2,
                    f"PRNG key '{name}' is consumed again (line {l1} "
                    f"already passed it to jax.random) without a "
                    "re-split; reusing a key yields correlated draws — "
                    "split/fold_in first"))

    @staticmethod
    def _assign_targets(n: ast.AST) -> Iterable[str]:
        targets: List[ast.AST] = []
        if isinstance(n, ast.Assign):
            targets = list(n.targets)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            targets = [n.target]
        elif isinstance(n, ast.For):
            targets = [n.target]
        elif isinstance(n, ast.NamedExpr):
            targets = [n.target]
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            targets = [n.optional_vars]
        for t in targets:
            if isinstance(t, ast.Name):
                yield t.id
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    if isinstance(e, ast.Name):
                        yield e.id

    @staticmethod
    def _sibling_branches(b1: tuple, b2: tuple) -> bool:
        for (n1, lbl1), (n2, lbl2) in zip(b1, b2):
            if n1 != n2:
                return False
            if lbl1 != lbl2:
                return True
        return False


# ------------------------------------------------------------------ DL006
FORWARD_MARK = "ledger-schema: forward"


def _is_ledger_receiver(func: ast.AST) -> bool:
    """Receiver of ``.emit`` looks like a ledger ('led' included so the
    natural short name cannot dodge the checker)."""
    if not isinstance(func, ast.Attribute):
        return False
    name = terminal_name(func.value).lower()
    return "ledger" in name or name == "led"


def check_emit_calls(ctx: FileContext, schema: Dict[str, tuple],
                     rule_id: str = "DL006") -> List[Finding]:
    """Every ``*ledger*.emit(...)`` call site names a declared event as a
    literal and passes all its required fields as explicit keywords (the
    former tools/check_ledger_schema.py walk, verbatim semantics —
    including the ``# ledger-schema: forward`` escape for wrappers that
    re-expose emit()'s own signature)."""
    out: List[Finding] = []
    for node in _calls(ctx.tree):
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "emit"
                and _is_ledger_receiver(f)):
            continue
        if FORWARD_MARK in ctx.line_text(node.lineno):
            continue
        mk = lambda msg: Finding(rule_id, ctx.rel, node.lineno,
                                 node.col_offset, msg)
        if not node.args:
            out.append(mk("emit() without an event argument"))
            continue
        ev = node.args[0]
        if not (isinstance(ev, ast.Constant) and isinstance(ev.value, str)):
            out.append(mk("event name must be a literal string "
                          "(static checkability)"))
            continue
        required = schema.get(ev.value)
        if required is None:
            out.append(mk(f"undeclared event {ev.value!r} "
                          f"(EVENT_SCHEMA: {sorted(schema)})"))
            continue
        kw = {k.arg for k in node.keywords if k.arg is not None}
        missing = [x for x in required if x not in kw]
        if missing:
            out.append(mk(f"event {ev.value!r} missing required "
                          f"keyword(s) {missing}"))
    return out


class LedgerSchema(Rule):
    id = "DL006"
    title = "ledger emit() schema conformance"
    rationale = ("schema drift — a renamed field, an undeclared event — "
                 "must fail at review time, not at 3am when someone greps "
                 "a ledger")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if ".emit(" not in ctx.src:
            return []
        schema = project.event_schema
        if not schema:
            return []
        return check_emit_calls(ctx, schema, self.id)


# ------------------------------------------------------------------ DL007
class DonatedBufferReuse(Rule):
    id = "DL007"
    title = "donated buffer referenced after the jitted call"
    rationale = ("donate_argnums hands the argument's device buffer to "
                 "XLA for reuse; reading the Python reference afterwards "
                 "returns garbage (or raises on deletion-checking "
                 "backends) — rebind or stop donating")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "donate_argnums" not in ctx.src:
            return []   # cheap text gate before any AST walking
        out: List[Finding] = []
        # module-level jit handles (`step = jax.jit(f, donate_argnums=..)`)
        # are visible to every function scope — collect them first
        module_donating: Dict[str, Tuple[int, ...]] = {}
        for stmt in ctx.tree.body:
            tgt, val = _assign_parts(stmt)
            if isinstance(tgt, ast.Name) and isinstance(val, ast.Call) \
                    and terminal_name(val.func) in ("jit", "pjit"):
                pos = self._donated_positions(val)
                if pos:
                    module_donating[tgt.id] = pos
        scopes = [ctx.tree] + [n for n in ast.walk(ctx.tree)
                               if isinstance(n, (ast.FunctionDef,
                                                 ast.AsyncFunctionDef))]
        for scope in scopes:
            self._check_scope(scope, ctx, out, dict(module_donating))
        return out

    @staticmethod
    def _donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
        for k in call.keywords:
            if k.arg == "donate_argnums":
                v = k.value
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)):
                    pos = tuple(e.value for e in v.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, int))
                    return pos or None
        return None

    def _check_scope(self, scope, ctx: FileContext, out: List[Finding],
                     donating: Optional[Dict[str, Tuple[int, ...]]] = None
                     ) -> None:
        body = scope.body if hasattr(scope, "body") else []
        donating = dict(donating or {})
        # ordering is by (line, col) against the call's END position —
        # args on continuation lines of a multi-line call sit inside the
        # span (not "after" it), and a same-line read past the closing
        # paren (`return f(s), s.step`) is a real post-donation use
        consumed: List[Tuple[str, int, Tuple[int, int], ast.AST]] = []
        assigns: Dict[str, List[Tuple[int, int]]] = {}
        reads: Dict[str, List[Tuple[Tuple[int, int], ast.AST]]] = {}

        def walk(n: ast.AST) -> None:
            for kid in ast.iter_child_nodes(n):
                if isinstance(kid, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    continue   # nested scopes get their own pass
                walk(kid)
            tgt, val = _assign_parts(n)
            if isinstance(tgt, ast.Name) and isinstance(val, ast.Call) \
                    and terminal_name(val.func) in ("jit", "pjit"):
                pos = self._donated_positions(val)
                if pos:
                    donating[tgt.id] = pos
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name) \
                    and n.func.id in donating:
                end = (n.end_lineno or n.lineno, n.end_col_offset or 0)
                for p in donating[n.func.id]:
                    if p < len(n.args) and isinstance(n.args[p], ast.Name):
                        consumed.append((n.args[p].id, n.lineno, end, n))
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, ast.Load):
                    reads.setdefault(n.id, []).append(
                        ((n.lineno, n.col_offset), n))
                else:
                    assigns.setdefault(n.id, []).append(
                        (n.lineno, n.col_offset))

        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            walk(stmt)
        for var, call_line, call_end, _ in consumed:
            rebinds = assigns.get(var, ())
            for read_pos, node in sorted(reads.get(var, ()),
                                         key=lambda r: r[0]):
                if read_pos <= call_end:
                    continue   # before the call, or one of its own args
                if any((call_line, -1) <= a < read_pos for a in rebinds):
                    continue   # state = step(state, ...) rebinding pattern
                out.append(self.finding(
                    ctx, node,
                    f"'{var}' was donated to a jitted call on line "
                    f"{call_line} (donate_argnums) and is read again here; "
                    "its device buffer may already be reused — rebind the "
                    "result or drop the donation"))
                break   # one finding per (var, donation) pair is enough
        return


# ------------------------------------------------------------------ DL008
class HotLoopDevicePut(Rule):
    uses_graph = True
    id = "DL008"
    title = "bare device_put on the hot step path"
    severity = "warn"
    rationale = ("a device_put dispatched from the step loop charges the "
                 "host->device copy to the consumer's critical path — the "
                 "data_s the round-9 DevicePrefetcher exists to hide; "
                 "stage uploads through data.loader (DevicePrefetcher / "
                 "prefetch_to_device) so the dispatch rides the producer "
                 "thread")

    # the loader IS the staging layer: its device_put/
    # make_array_from_process_local_data call sites are the one legitimate
    # home for hot-path uploads (every engine rides them via
    # prefetch_to_device / stream_prefetch)
    LOADER_FILES = {"tpu_dist/data/loader.py"}
    PUT_QUALS = {"jax.device_put", "device_put"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "device_put" not in ctx.src:
            return []   # cheap text gate before opening the graph
        if ctx.rel.replace("\\", "/") in self.LOADER_FILES:
            return []
        helper = RULES_BY_ID["DL002"]   # shares the derived hot set
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            reaches = g.reaches_traced()
            traced = g.traced_funcs()
            hot = helper._hot_funcs(g, reaches, traced)
            for node in g.file_nodes(ctx.rel):
                if node.qual in traced:
                    continue
                for loop in node.loops:
                    if helper._loop_is_hot(node, loop, g, reaches, traced):
                        for stmt in loop.body + loop.orelse:
                            self._scan_stmt(stmt, node.name, ctx, out,
                                            lexical=True)
                if node.node is not None and node.qual in hot:
                    for stmt in node.node.body:
                        self._scan_stmt(stmt, node.name, ctx, out,
                                        lexical=False)
        seen: Set[Tuple[int, int]] = set()
        uniq: List[Finding] = []
        for f in sorted(out, key=lambda f: (f.line, f.col)):
            if (f.line, f.col) not in seen:
                seen.add((f.line, f.col))
                uniq.append(f)
        return uniq

    def _scan_stmt(self, stmt: ast.stmt, fn_name: str, ctx: FileContext,
                   out: List[Finding], lexical: bool) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # separate node: the reachability pass covers it
        for child in ast.iter_child_nodes(stmt):
            self._scan_stmt(child, fn_name, ctx, out, lexical)
        if isinstance(stmt, ast.Call) \
                and ctx.resolve(dotted_name(stmt.func)) in self.PUT_QUALS:
            where = (f"inside the hot loop of {fn_name}()" if lexical
                     else f"in {fn_name}(), reachable from a hot step loop")
            out.append(self.finding(
                ctx, stmt,
                f"bare device_put {where}: the upload dispatch runs on "
                "the consumer thread and lands in data_s — stage it "
                "through data.loader.DevicePrefetcher/prefetch_to_device "
                "(or pin with a reason if this copy is deliberate)"))


# ------------------------------------------------ DL101-DL104 concurrency
class SignalLockDeadlock(Rule):
    uses_graph = True
    id = "DL101"
    title = "plain Lock on a signal-handler path"
    rationale = ("a signal handler runs ON the main thread between "
                 "bytecodes; if it acquires a non-reentrant "
                 "threading.Lock that the interrupted main-thread code "
                 "was holding (the emit/sink fan-out), the process "
                 "self-deadlocks — exactly the PR-5 Ledger SIGTERM bug. "
                 "Use threading.RLock for any lock visible to a handler")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "Lock(" not in ctx.src:
            return []   # cheap text gate: no lock construction here
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            plain = {key: kind for key, kind in g.lock_attrs.items()
                     if key[0][0] == ctx.rel and kind == "Lock"}
            if not plain:
                return out
            hr = g.handler_reachable()
            ml = g.mainline_reachable()
            # acquire sites per (clskey, attr) in this file
            sites: Dict[tuple, List[tuple]] = {}
            for node in g.file_nodes(ctx.rel):
                if node.cls is None:
                    continue
                for owner, attr, line, col in node.lock_acquires:
                    if owner == "self" and (node.cls, attr) in plain:
                        sites.setdefault((node.cls, attr), []).append(
                            (node, line, col))
            for key, acqs in sites.items():
                handler_acqs = [a for a in acqs if a[0].qual in hr]
                main_acqs = [a for a in acqs if a[0].qual in ml]
                if not handler_acqs or not main_acqs:
                    continue
                clskey, attr = key
                for node, line, col in handler_acqs:
                    out.append(Finding(
                        self.id, ctx.rel, line, col,
                        f"non-reentrant threading.Lock "
                        f"'{clskey[1]}.{attr}' is acquired in "
                        f"{node.name}(), which is reachable from a signal "
                        f"handler, while the same lock guards main-thread "
                        f"call sites (e.g. {main_acqs[0][0].name}()); a "
                        "signal landing while the main thread holds it "
                        "self-deadlocks — use threading.RLock"))
        return out


class BlockingIoUnderLock(Rule):
    uses_graph = True
    id = "DL102"
    title = "blocking I/O while holding a shared lock"
    rationale = ("a sink/emit lock held across subprocess/socket/HTTP "
                 "calls or sleeps stalls every hot-path emit() caller "
                 "behind one slow syscall; move the I/O outside the "
                 "critical section (snapshot under the lock, write after)")
    severity = "warn"

    BLOCKING_IO_QUALS = {
        "time.sleep", "os.system",
        "subprocess.run", "subprocess.Popen", "subprocess.call",
        "subprocess.check_call", "subprocess.check_output",
        "socket.create_connection", "urllib.request.urlopen",
        "requests.get", "requests.post", "requests.request",
        "http.client.HTTPConnection", "http.client.HTTPSConnection",
    }
    # function names that put a method on the emit fan-out even when no
    # reachability evidence exists (ledger sinks are duck-typed callables)
    EMITISH = {"emit", "sink", "__call__"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "Lock(" not in ctx.src:
            return []   # cheap text gate: no lock construction here
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            known = {key for key in g.lock_attrs if key[0][0] == ctx.rel}
            if not known:
                return out
            ml = g.mainline_reachable()
            acq_funcs: Dict[tuple, List] = {}
            for node in g.file_nodes(ctx.rel):
                if node.cls is None:
                    continue
                for owner, attr, _, _ in node.lock_acquires:
                    if owner == "self" and (node.cls, attr) in known:
                        acq_funcs.setdefault((node.cls, attr),
                                             []).append(node)
            for key, nodes in acq_funcs.items():
                on_emit_path = any(n.qual in ml or n.name in self.EMITISH
                                   for n in nodes)
                if not on_emit_path:
                    continue
                for node in nodes:
                    self._scan_with_blocks(node, key[1], ctx, out)
        return out

    def _scan_with_blocks(self, node, attr: str, ctx: FileContext,
                          out: List[Finding]) -> None:
        if node.node is None:
            return
        for n in ast.walk(node.node):
            if not isinstance(n, (ast.With, ast.AsyncWith)):
                continue
            holds = any(
                isinstance(i.context_expr, ast.Attribute)
                and terminal_name(i.context_expr) == attr
                and isinstance(i.context_expr.value, ast.Name)
                and i.context_expr.value.id == "self"
                for i in n.items)
            if not holds:
                continue
            for call in _calls_same_scope(n):
                qual = ctx.resolve(dotted_name(call.func))
                if qual in self.BLOCKING_IO_QUALS:
                    out.append(self.finding(
                        ctx, call,
                        f"blocking call '{qual}' executes while holding "
                        f"'self.{attr}', a lock the emit fan-out also "
                        "takes; every hot-path emitter stalls behind this "
                        "syscall — snapshot under the lock, do the I/O "
                        "after releasing it"))


class NonDaemonThreadNoJoin(Rule):
    uses_graph = True
    id = "DL103"
    title = "non-daemon thread with no join"
    rationale = ("a non-daemon thread with no join anywhere keeps the "
                 "interpreter alive after a crash: the run is dead, the "
                 "pod is billed, and the scheduler sees a healthy "
                 "process. Mark helpers daemon=True, or join the thread "
                 "on the shutdown path")
    severity = "warn"

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "Thread(" not in ctx.src:
            return []   # cheap text gate: no thread construction here
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            recs = g.thread_ctors.get(ctx.rel, ())
            if not recs:
                return out
            # join matching is FILE-scoped on the receiver name: a
            # Watchdog joining its own '_thread' must not vouch for an
            # unrelated class's '_thread' in another file
            file_joins = {recv for qual, recv in g.join_sites
                          if qual.startswith(ctx.rel + "::")}
            # functions that join SOMETHING: a create-start-join worker
            # pattern in one function is bounded-lifetime, even when the
            # ctor (a comprehension, say) can't be bound to the receiver
            joining_funcs = {qual for qual, _ in g.join_sites}
            for rec in recs:
                if rec["daemon_true"]:
                    continue
                bind = rec["bind"]
                if bind and bind in file_joins:
                    continue
                if rec["qual"] in joining_funcs:
                    continue
                what = (f"thread bound to {bind!r}" if bind
                        else "unbound thread (constructed and started "
                             "inline)")
                out.append(Finding(
                    self.id, ctx.rel, rec["lineno"], rec["col"],
                    f"threading.Thread without daemon=True and without a "
                    f"join ({what}): if the run crashes, this thread "
                    "keeps the process alive forever — pass daemon=True "
                    "or join it on the run_end/shutdown path"))
        return out


class SignalHandlerHygiene(Rule):
    uses_graph = True
    id = "DL104"
    title = "unsafe signal handler body / dropped prior handler"
    rationale = ("logging and stream .flush() are not async-signal-safe "
                 "(a handler interrupting the io stack re-enters it and "
                 "corrupts or deadlocks); and installing a handler while "
                 "discarding signal.signal's return value silently drops "
                 "a previously-installed hook (a preemption checkpointer, "
                 "say) — capture and chain it")

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            handlers = {q for q in g.signal_handlers()
                        if q.startswith(ctx.rel + "::")}
            # the text gate only closes the file when the HANDLER root
            # set (memoized, cross-file) has nothing here either: a
            # handler body may live in a file that never says 'signal'
            # (installed from elsewhere), and install-site checks below
            # require the literal text by construction
            if not handlers and "signal" not in ctx.src:
                return out
            for node in g.file_nodes(ctx.rel):
                if node.qual in handlers and node.node is not None:
                    self._scan_handler_body(node, ctx, out)
            for rec in g.signal_installs.get(ctx.rel, ()):
                self._check_chaining(rec, g, ctx, out)
        return out

    def _scan_handler_body(self, node, ctx: FileContext,
                           out: List[Finding]) -> None:
        for call in _calls_same_scope(node.node):
            qual = ctx.resolve(dotted_name(call.func))
            tname = terminal_name(call.func)
            hit = None
            if qual.split(".")[0] == "logging" or (
                    qual.startswith("log") and tname in (
                        "debug", "info", "warning", "error", "exception",
                        "critical")):
                hit = f"logging call '{qual}'"
            elif tname == "flush":
                hit = f"stream flush '{dotted_name(call.func)}()'"
            if hit:
                out.append(self.finding(
                    ctx, call,
                    f"{hit} inside the signal handler {node.name}(): "
                    "logging/io are not reentrant — a signal landing "
                    "mid-write re-enters the io stack and corrupts or "
                    "deadlocks; set a flag and do the work on the main "
                    "code path"))

    def _check_chaining(self, rec, g, ctx: FileContext,
                        out: List[Finding]) -> None:
        if rec["result_used"]:
            return
        handler = rec["handler"]
        installs_new = isinstance(handler, ast.Lambda)
        if isinstance(handler, (ast.Name, ast.Attribute)):
            node = g.funcs.get(rec["qual"])
            if node is not None:
                targets, _ = g.resolve(node, dotted_name(handler))
                installs_new = bool(targets)
        if installs_new:
            out.append(Finding(
                self.id, ctx.rel, rec["lineno"], rec["col"],
                "signal.signal() installs a new handler but discards the "
                "return value: any previously-installed handler (a "
                "preemption checkpoint hook, a supervisor's own cleanup) "
                "is silently dropped — capture the previous handler and "
                "chain it from yours"))


# ------------------------------------------------------------------ DL201
class DivergentBranchCollectives(Rule):
    uses_graph = True
    id = "DL201"
    title = "cond/switch branches issue divergent collective sequences"
    rationale = ("under SPMD every process must execute the SAME ordered "
                 "collective sequence; if lax.cond branches disagree (psum "
                 "then pmax vs pmax then psum, or a collective in one arm "
                 "only) any per-process predicate divergence pairs "
                 "mismatched collectives across hosts and the pod "
                 "deadlocks — the MPI matching rule, provable statically")

    # primitives that rendezvous across processes when traced: the jaxpr
    # half of this check lives in tpu_dist/analysis/proglint.py (PL002);
    # this is the source-level prover over the same failure class
    COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
                   "all_to_all", "ppermute", "pbroadcast", "psum_scatter",
                   "axis_index"}
    _BRANCH_CALLS = {"cond", "switch"}

    def check(self, ctx: FileContext, project: Project) -> List[Finding]:
        if "cond" not in ctx.src and "switch" not in ctx.src:
            return []
        out: List[Finding] = []
        with graph_scope(project, ctx) as g:
            for node in g.file_nodes(ctx.rel):
                root = ctx.tree if node.name == "<module>" else node.node
                if root is None:
                    continue
                for call in _calls_same_scope(root):
                    if terminal_name(call.func) in self._BRANCH_CALLS:
                        self._check_site(call, node, g, ctx, out)
        return out

    def _check_site(self, call: ast.Call, encl, g, ctx: FileContext,
                    out: List[Finding]) -> None:
        tname = terminal_name(call.func)
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        if tname == "cond":
            branches = list(call.args[1:3])
            for name in ("true_fun", "false_fun"):
                if name in kw:
                    branches.append(kw[name])
            labels = ("true branch", "false branch")
            if len(branches) != 2:
                return
        else:
            seq_arg = (call.args[1] if len(call.args) > 1
                       else kw.get("branches"))
            if not isinstance(seq_arg, (ast.Tuple, ast.List)):
                return
            branches = list(seq_arg.elts)
            labels = tuple(f"branch[{i}]" for i in range(len(branches)))
            if len(branches) < 2:
                return
        seqs = []
        for b in branches:
            seq = self._branch_sequence(b, encl, g)
            if seq is None:
                return   # unresolvable callable: stay silent, no guess
            seqs.append(seq)
        if len(set(seqs)) <= 1 or not any(seqs):
            return
        desc = "; ".join(f"{lab} {self._fmt(s)}"
                         for lab, s in zip(labels, seqs))
        out.append(self.finding(
            ctx, call,
            f"lax.{tname} branches issue different ordered collective "
            f"sequences ({desc}); a process taking the other branch "
            "pairs mismatched collectives across hosts and the pod "
            "deadlocks — make every branch issue the identical sequence "
            "(pad with the same collectives on a zero operand if needed)"))

    def _branch_sequence(self, node: ast.AST, encl, g,
                         _depth: int = 0,
                         _seen: Optional[Set[str]] = None):
        """Ordered (collective, axes...) tuples a branch callable issues,
        or None when the callable cannot be resolved. Name/Attribute refs
        resolve through the call graph (one level of helper recursion,
        cycle-guarded); lambdas and functools.partial heads inline."""
        if _seen is None:
            _seen = set()
        if isinstance(node, ast.Lambda):
            return self._sequence(node, encl, g, _depth, _seen)
        if (isinstance(node, ast.Call)
                and terminal_name(node.func) == "partial" and node.args):
            return self._branch_sequence(node.args[0], encl, g,
                                         _depth, _seen)
        if isinstance(node, (ast.Name, ast.Attribute)):
            targets, _ = g.resolve(encl, dotted_name(node))
            for t in targets:
                fn = g.funcs.get(t)
                if fn is not None and fn.node is not None:
                    if t in _seen:
                        return ()
                    _seen.add(t)
                    return self._sequence(fn.node, fn, g, _depth, _seen)
        return None

    def _sequence(self, root: ast.AST, owner, g, depth: int,
                  seen: Set[str]) -> tuple:
        calls = sorted(_calls_same_scope(root),
                       key=lambda c: (c.lineno, c.col_offset))
        seq: List[tuple] = []
        for c in calls:
            tn = terminal_name(c.func)
            if tn in self.COLLECTIVES:
                seq.append((tn,) + self._axes(c, tn))
            elif depth < 1 and owner is not None:
                targets, _ = g.resolve(owner, dotted_name(c.func))
                for t in targets:
                    fn = g.funcs.get(t)
                    if fn is not None and fn.node is not None \
                            and t not in seen:
                        seen.add(t)
                        seq.extend(self._sequence(fn.node, fn, g,
                                                  depth + 1, seen))
                        break
        return tuple(seq)

    def _axes(self, call: ast.Call, tname: str) -> tuple:
        pos = 0 if tname in ("axis_index", "axis_size") else 1
        cands = list(call.args[pos:pos + 1]) + [
            k.value for k in call.keywords
            if k.arg in ("axis_name", "axis", "axes")]
        out: List[str] = []

        def walk(nodes) -> None:
            for n in nodes:
                if isinstance(n, (ast.Tuple, ast.List)):
                    walk(n.elts)
                elif isinstance(n, ast.Constant) and isinstance(n.value,
                                                                str):
                    out.append(n.value)
        walk(cands)
        return tuple(out)

    def _fmt(self, seq: tuple) -> str:
        if not seq:
            return "[no collectives]"
        return "[" + " -> ".join(
            f"{s[0]}({','.join(s[1:])})" for s in seq) + "]"


RULES: List[Rule] = [HostDivergentCollectives(), HotLoopHostSync(),
                     UnknownMeshAxis(), TracedSideEffect(), PrngHygiene(),
                     LedgerSchema(), DonatedBufferReuse(),
                     HotLoopDevicePut(),
                     SignalLockDeadlock(), BlockingIoUnderLock(),
                     NonDaemonThreadNoJoin(), SignalHandlerHygiene(),
                     DivergentBranchCollectives()]

RULES_BY_ID: Dict[str, Rule] = {r.id: r for r in RULES}
