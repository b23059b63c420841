"""proglint — the program-level SPMD auditor (jaxpr + compiled artifact).

tools/distlint proves source-level hazards by AST; this module audits what
only exists AFTER tracing: the jaxpr the step compiler built and the
executable XLA handed back. The reference's worst bugs were exactly this
class — silently wrong *programs* (the apex prefetcher corrupting its
stream, horovod double-averaging gradients), not wrong source lines.

Checks (each waivable through a distlint-style reason-required file):

=====  =======  ===========================================================
id     surface  hazard
=====  =======  ===========================================================
PL001  jaxpr    a collective equation runs over an axis name outside the
                parallel/mesh.py authority (the program twin of DL003)
PL002  jaxpr    cond branches issue DIFFERENT ordered collective
                sequences — under SPMD each device resolves the predicate
                locally, so divergent orders are a deadlock at runtime,
                provable statically (the MPI-matching rule; while bodies
                are exempt: one body, same trip count on every device)
PL003  HLO      declared donate_argnums not aliased in the compiled
                module — XLA silently drops donation on sharding/layout
                mismatch and the program runs with DOUBLE the state HBM
PL004  jaxpr    f32/f64 compute (dot/conv) inside a program the config
                declares bf16/int8 — a promotion leak that quietly
                refunds the precision win
PL005  runtime  trace-cache growth past the program's allowed shape
                count — a shape/dtype varying per dispatch retraces on
                the hot path (checked at drain boundaries only)
PL000  meta     a waiver without a written reason (debt is named, or it
                is a bug)
=====  =======  ===========================================================

Waiver grammar (default file ``scripts/proglint_waivers.txt``)::

    PLNNN <program-glob> -- reason text

Import discipline: jax loads lazily inside the tracing helpers, so waiver
parsing and finding/report rendering work on a bare host (the
tools/distlint convention).
"""

from __future__ import annotations

import fnmatch
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CHECKS = {
    "PL000": ("waiver hygiene",
              "a waiver with no written reason hides debt instead of "
              "naming it"),
    "PL001": ("unknown collective axis",
              "a collective equation uses an axis name outside the "
              "parallel/mesh.py authority"),
    "PL002": ("divergent branch collective order",
              "cond branches issue different ordered collective sequences "
              "— an SPMD deadlock, provable statically"),
    "PL003": ("dropped buffer donation",
              "declared donate_argnums not aliased in the compiled module "
              "(XLA drops donation silently on sharding/layout mismatch)"),
    "PL004": ("precision promotion leak",
              "f32/f64 dot/conv compute inside a program declared "
              "bf16/int8"),
    "PL005": ("hot-path recompilation",
              "the program's trace cache grew past its allowed shape "
              "count — a shape/dtype varies per dispatch"),
}

#: primitives whose equations carry a mesh axis (axes= on the psum/pmin/
#: pmax family, axis_name= on the rest). Under shard_map's check_vma the
#: replicated-output forms trace as ``psum_invariant`` /
#: ``all_gather_invariant``. NOT a dtype/shape reduction like reduce_sum,
#: whose ``axes`` are positional ints — the walker only reads axis params
#: from this set and keeps string values only.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmin", "pmax", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "pbroadcast",
    "reduce_scatter", "axis_index",
})

#: compute-heavy primitives PL004 holds to the declared precision
_COMPUTE_PRIMS = frozenset({"dot_general", "conv_general_dilated"})

#: config precisions that declare a low-precision compute program.
#: ("bf16_params" keeps f32 compute on purpose — master-weights style —
#: so it is NOT in this set.)
LOW_PRECISION = frozenset({"bf16", "int8"})

DEFAULT_WAIVERS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "proglint_waivers.txt")


class AuditError(RuntimeError):
    """Raised under ``audit=halt`` when a program carries unwaivered
    findings (compile-time checks) or trips the recompile sentry."""


@dataclass
class Finding:
    """One audit finding against one program."""

    check: str
    program: str
    message: str
    waived: bool = False
    reason: str = ""

    def render(self) -> str:
        tag = f" [waived: {self.reason}]" if self.waived else ""
        return (f"{self.program}: {self.check} "
                f"{CHECKS[self.check][0]}: {self.message}{tag}")

    def to_json(self) -> dict:
        return {"check": self.check, "program": self.program,
                "message": self.message, "waived": self.waived,
                "reason": self.reason}


# ---- waivers ---------------------------------------------------------------

@dataclass(frozen=True)
class Waiver:
    check: str
    pattern: str      # fnmatch glob over the program name
    reason: str
    line: int = 0


def parse_waivers(text: str,
                  origin: str = "<waivers>") -> Tuple[List[Waiver],
                                                      List[Finding]]:
    """Parse the waiver grammar. A syntactically-valid waiver missing its
    ``-- reason`` is returned as a PL000 finding, not silently honored —
    the reason requirement is the whole point of the grammar."""
    waivers: List[Waiver] = []
    meta: List[Finding] = []
    for i, raw in enumerate(text.splitlines(), 1):
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        head, sep, reason = line.partition("--")
        parts = head.split()
        if len(parts) != 2 or parts[0] not in CHECKS:
            meta.append(Finding("PL000", origin,
                                f"line {i}: unparseable waiver {raw!r} "
                                "(grammar: 'PLNNN <program-glob> -- "
                                "reason')"))
            continue
        reason = reason.strip()
        if not sep or not reason:
            meta.append(Finding("PL000", origin,
                                f"line {i}: waiver for {parts[0]} on "
                                f"{parts[1]!r} has no reason"))
            continue
        waivers.append(Waiver(parts[0], parts[1], reason, i))
    return waivers, meta


def load_waivers(path: Optional[str] = None
                 ) -> Tuple[List[Waiver], List[Finding]]:
    path = DEFAULT_WAIVERS if path is None else path
    if not os.path.exists(path):
        return [], []
    with open(path) as f:
        return parse_waivers(f.read(), origin=os.path.basename(path))


def apply_waivers(findings: Iterable[Finding],
                  waivers: Sequence[Waiver]) -> List[Finding]:
    """Mark each finding waived when a (check, program-glob) waiver
    matches; findings are returned (same objects) for chaining."""
    out = list(findings)
    for f in out:
        for w in waivers:
            if w.check == f.check and fnmatch.fnmatch(f.program, w.pattern):
                f.waived, f.reason = True, w.reason
                break
    return out


def unwaivered(findings: Iterable[Finding]) -> List[Finding]:
    return [f for f in findings if not f.waived]


# ---- jaxpr walking ---------------------------------------------------------

def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (pjit/shard_map jaxpr=,
    cond branches=, scan/while bodies, custom_vjp call_jaxpr, ...)."""
    from jax.extend import core

    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, core.Jaxpr):
                yield x


def iter_eqns(jaxpr):
    """Depth-first over every equation, including nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _axis_names(eqn) -> Tuple[str, ...]:
    """The mesh-axis names a collective equation runs over. The psum family
    spells them ``axes=``, the rest ``axis_name=``; both may be a bare
    string or a tuple, and non-string entries (positional reduce axes)
    are not mesh axes."""
    v = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(v, str):
        v = (v,)
    return tuple(x for x in (v or ()) if isinstance(x, str))


def collective_signature(jaxpr) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
    """The ordered (primitive, axes) sequence of every collective in the
    jaxpr, nested bodies included — the thing PL002 compares across
    branches (MPI-matching: order IS the correctness condition)."""
    return tuple((eqn.primitive.name, _axis_names(eqn))
                 for eqn in iter_eqns(jaxpr)
                 if eqn.primitive.name in COLLECTIVE_PRIMS)


def mesh_axis_authority() -> frozenset:
    """The declared axis names, by reflection over parallel/mesh.py (the
    same authority distlint's DL003 AST-extracts)."""
    from tpu_dist.parallel import mesh as mesh_mod

    return frozenset(v for k, v in vars(mesh_mod).items()
                     if k.endswith("_AXIS") and isinstance(v, str))


# ---- the jaxpr checks ------------------------------------------------------

def _check_axes(program: str, jaxpr, authority) -> List[Finding]:
    unknown: Dict[str, str] = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            for ax in _axis_names(eqn):
                if ax not in authority:
                    unknown.setdefault(ax, eqn.primitive.name)
    return [Finding("PL001", program,
                    f"collective {prim} over axis {ax!r} not in the mesh "
                    f"authority {sorted(authority)}")
            for ax, prim in sorted(unknown.items())]


def _check_branches(program: str, jaxpr) -> List[Finding]:
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "cond":
            continue
        sigs = [collective_signature(br.jaxpr)
                for br in eqn.params["branches"]]
        if any(sigs) and len(set(sigs)) > 1:
            shown = [" -> ".join(f"{p}{list(a)}" for p, a in s) or "(none)"
                     for s in sigs]
            out.append(Finding(
                "PL002", program,
                "cond branches issue divergent collective sequences: "
                + " VS ".join(shown)))
    return out


def _check_precision(program: str, jaxpr,
                     precision: Optional[str]) -> List[Finding]:
    if precision not in LOW_PRECISION:
        return []
    import numpy as np

    leaks: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        try:
            out_dtypes = [np.dtype(v.aval.dtype) for v in eqn.outvars
                          if hasattr(v.aval, "dtype")]
            in_dtypes = [np.dtype(v.aval.dtype) for v in eqn.invars
                         if hasattr(v.aval, "dtype")]
        except Exception:
            continue
        if any(d == np.float64 for d in out_dtypes):
            leaks[f"{name}:f64"] = leaks.get(f"{name}:f64", 0) + 1
        elif (name in _COMPUTE_PRIMS and in_dtypes
                and all(d == np.float32 for d in in_dtypes)):
            leaks[f"{name}:f32"] = leaks.get(f"{name}:f32", 0) + 1
    return [Finding("PL004", program,
                    f"{n} {prim.split(':')[0]} equation(s) compute in "
                    f"{prim.split(':')[1]} inside a {precision} program")
            for prim, n in sorted(leaks.items())]


def _donation_declared(jaxpr) -> bool:
    for eqn in iter_eqns(jaxpr):
        if any(eqn.params.get("donated_invars") or ()):
            return True
    return False


def donation_aliased(hlo_text: str) -> bool:
    """Whether the compiled module's header carries any input/output
    alias. XLA states donation in the one-line ``HloModule`` header
    (``input_output_alias={ {}: (0, {}, may-alias) }``) and OMITS the
    field entirely when every donation was dropped."""
    head = hlo_text.splitlines()[0] if hlo_text else ""
    return "input_output_alias=" in head


def _check_donation(program: str, jaxpr,
                    hlo: Optional[str]) -> List[Finding]:
    if hlo is None or not _donation_declared(jaxpr):
        return []
    if donation_aliased(hlo):
        return []
    return [Finding(
        "PL003", program,
        "donate_argnums declared but the compiled module aliases NO "
        "buffer — donation was dropped (sharding/layout mismatch) and "
        "the state is double-buffered in HBM")]


def audit_jaxpr(program: str, closed, *, authority=None,
                precision: Optional[str] = None,
                hlo: Optional[str] = None) -> List[Finding]:
    """The compile-time pass over one traced program: PL001 + PL002 +
    PL004 on the jaxpr, PL003 against the compiled module's header when
    the caller has it (engines pass telemetry.program_stats' HLO text —
    no extra lowering). ``closed`` is a ClosedJaxpr or Jaxpr."""
    jaxpr = getattr(closed, "jaxpr", closed)
    authority = mesh_axis_authority() if authority is None else authority
    findings = _check_axes(program, jaxpr, authority)
    findings += _check_branches(program, jaxpr)
    findings += _check_precision(program, jaxpr, precision)
    findings += _check_donation(program, jaxpr, hlo)
    return findings


# ---- the runtime sentry (PL005) -------------------------------------------

class RecompileSentry:
    """Per-program trace-cache watch. ``register`` is idempotent (first
    dispatch re-registers freely); ``check`` is a host-only counter read
    sized for drain boundaries — no device sync, no tracing — and
    latches one finding per program so ``record`` mode emits exactly one
    ``audit`` event per offender."""

    def __init__(self):
        self._programs: Dict[str, dict] = {}

    def register(self, program: str, fn, allowed: int = 1) -> None:
        rec = self._programs.setdefault(
            program, {"fn": fn, "allowed": allowed, "flagged": False})
        rec["fn"] = fn
        rec["allowed"] = max(rec["allowed"], allowed)

    def check(self) -> List[Finding]:
        out = []
        for name in sorted(self._programs):
            rec = self._programs[name]
            size_fn = getattr(rec["fn"], "_cache_size", None)
            if size_fn is None or rec["flagged"]:
                continue
            n = size_fn()
            if n > rec["allowed"]:
                rec["flagged"] = True
                out.append(Finding(
                    "PL005", name,
                    f"trace cache holds {n} entries (allowed "
                    f"{rec['allowed']}): a shape/dtype is varying per "
                    "dispatch and every variation recompiles on the hot "
                    "path"))
        return out


# ---- tune-space audit (satellite: every plan the repo can execute) --------

def _structural_key(plan) -> tuple:
    """Plans that trace to the SAME program: quant_block/opt_block_rows/
    fused_quant only move Pallas block params (trace-time constants) —
    auditing one representative per key covers the whole space."""
    return (plan.engine, plan.sync, plan.layout, plan.tp_impl, plan.quant,
            plan.window, plan.steps_per_dispatch, plan.grad_bucket_mb > 0,
            plan.grad_accum_steps, plan.donate)


def _program_name(plan) -> str:
    return (f"{plan.engine}/{plan.sync}/quant={plan.quant}"
            f"/window={plan.window}"
            + (f"x{plan.steps_per_dispatch}"
               if plan.window != "none" else "")
            + ("/bucketed" if plan.grad_bucket_mb > 0 else ""))


def _tiny_lm_fixture(quant: str):
    """The 1-layer/32-dim trace fixture (tests/test_plan.py recipe):
    enough structure for every knob in the space, cheap enough to trace
    the whole deduped space inside the tier-1 budget."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.engine.state import TrainState
    from tpu_dist.models.transformer import tiny_lm
    from tpu_dist.ops import make_optimizer

    V, L, D = 32, 16, 32
    model = tiny_lm(vocab_size=V, num_layers=1, d_model=D, num_heads=4,
                    max_len=L, quant=quant)
    rng = jax.random.PRNGKey(0)
    params = model.init({"params": rng},
                        np.zeros((1, L), np.int32), train=False)["params"]
    tx = make_optimizer(0.01, 0.9, 0.0)
    state = TrainState.create(jax.tree.map(jnp.copy, params), {}, tx)
    rows = np.random.RandomState(0).randint(0, V, (8, L + 1)).astype(
        np.int32)
    return model, tx, state, rows, rng


def audit_tune_space(space=None, *, waivers_path: Optional[str] = None,
                     devices: int = 8) -> dict:
    """Trace + audit every structurally-distinct program in the tuner's
    candidate space (CPU, abstract tracing only — nothing executes) and
    return a canonical, byte-deterministic report dict. Every plan is
    accounted for: ``plans`` counts the space, ``programs`` the deduped
    trace set — no silent caps."""
    import numpy as np

    import jax

    from tpu_dist.parallel.mesh import make_mesh
    from tpu_dist.plan.compile import (Bindings, activate_plan,
                                       compile_train_step)
    from tpu_dist.plan.tune import default_space

    if space is None:
        space = default_space("lm", devices)
    mesh = make_mesh((devices,), ("data",),
                     devices=jax.devices()[:devices])
    groups: Dict[tuple, list] = {}
    for plan in space:
        groups.setdefault(_structural_key(plan), []).append(plan)

    findings: List[Finding] = []
    programs: List[str] = []
    fixtures: Dict[str, tuple] = {}
    try:
        for key in sorted(groups, key=repr):
            plan = groups[key][0]
            if plan.quant not in fixtures:
                fixtures[plan.quant] = _tiny_lm_fixture(plan.quant)
            model, tx, state, rows, rng = fixtures[plan.quant]
            name = _program_name(plan)
            programs.append(name)
            activate_plan(plan)
            step = compile_train_step(plan, Bindings(mesh=mesh, model=model,
                                                     tx=tx))
            if plan.window == "none":
                args = (state, rows[:, :-1], rows[:, 1:], rng)
            else:
                k = plan.steps_per_dispatch
                big = np.tile(rows, (k, 1))
                idx = np.arange(k * 8, dtype=np.int32).reshape(k, 8)
                args = (state, big, idx, rng)
            closed = jax.make_jaxpr(step)(*args)
            findings += audit_jaxpr(name, closed)
    finally:
        # restore the plan-owned trace-time globals (the
        # clean_plan_globals contract in tests/test_plan.py)
        from tpu_dist.ops import pallas_adamw, pallas_quant, pallas_sgd
        from tpu_dist.ops.quant import set_fused_quant

        set_fused_quant(None)
        pallas_quant.set_quant_blocks()
        pallas_sgd.set_block_rows()
        pallas_adamw.set_block_rows()

    waivers, meta = load_waivers(waivers_path)
    findings = apply_waivers(findings, waivers) + meta
    findings.sort(key=lambda f: (f.program, f.check, f.message))
    return {
        "plans": len(space),
        "programs": len(programs),
        "program_names": programs,
        "findings": [f.to_json() for f in findings],
        "unwaivered": len(unwaivered(findings)),
    }


# ---- report side (mirrors tools/distlint/report.py) -----------------------

SARIF_SCHEMA = "https://json.schemastore.org/sarif-2.1.0.json"
SARIF_VERSION = "2.1.0"


def to_sarif(findings: Sequence[Finding]) -> dict:
    """SARIF 2.1.0 document, same shape as distlint's (driver name is
    the only divergence) so one CI code-scanning upload handles both."""
    rules_meta = [{
        "id": cid,
        "shortDescription": {"text": CHECKS[cid][0]},
        "fullDescription": {"text": CHECKS[cid][1]},
        "defaultConfiguration": {"level": "error"},
    } for cid in sorted(CHECKS)]
    results = [{
        "ruleId": f.check,
        "level": "note" if f.waived else "error",
        "message": {"text": f.message
                    + (f" [waived: {f.reason}]" if f.waived else "")},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {
                    "uri": f"programs/{f.program}",
                    "uriBaseId": "SRCROOT",
                },
                "region": {"startLine": 1, "startColumn": 1},
            },
        }],
    } for f in findings]
    return {
        "$schema": SARIF_SCHEMA,
        "version": SARIF_VERSION,
        "runs": [{
            "tool": {"driver": {"name": "proglint",
                                "rules": rules_meta}},
            "results": results,
        }],
    }


def _main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m tpu_dist.analysis.proglint",
        description="audit every program in the tuner's candidate space")
    parser.add_argument("--tune-space", default=None, metavar="FILE",
                        help="comm_bench measurement JSON naming the "
                        "device kind (scripts/tune_ci.json); the audited "
                        "space is the tuner's enumeration for it")
    parser.add_argument("--devices", type=int, default=8,
                        help="virtual CPU device count for the trace mesh")
    parser.add_argument("--waivers", default=None,
                        help=f"waiver file (default {DEFAULT_WAIVERS})")
    parser.add_argument("--json", default=None, metavar="FILE",
                        help="write the canonical report JSON here "
                        "('-' for stdout)")
    parser.add_argument("--sarif-out", default=None, metavar="FILE",
                        help="write a SARIF 2.1.0 artifact here")
    args = parser.parse_args(argv)

    # same virtual-device setup as tests/conftest.py, before any backend
    # initializes
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tpu_dist._compat import set_cpu_device_count

    set_cpu_device_count(max(args.devices, 1))

    from tpu_dist.plan.tune import default_space

    devices = args.devices
    if args.tune_space:
        with open(args.tune_space) as f:
            json.load(f)     # existence + shape check only: the space is
        #                      the tuner's enumeration, not the trials
    space = default_space("lm", devices)
    report = audit_tune_space(space, waivers_path=args.waivers,
                              devices=devices)
    text = json.dumps(report, indent=1, sort_keys=True) + "\n"
    if args.json == "-":
        print(text, end="")
    elif args.json:
        with open(args.json, "w") as f:
            f.write(text)
    if args.sarif_out:
        findings = [Finding(**d) for d in report["findings"]]
        with open(args.sarif_out, "w") as f:
            json.dump(to_sarif(findings), f, indent=2, sort_keys=True)
            f.write("\n")
    for d in report["findings"]:
        print(Finding(**d).render())
    print(f"proglint: {report['plans']} plan(s) -> {report['programs']} "
          f"distinct program(s), {len(report['findings'])} finding(s), "
          f"{report['unwaivered']} unwaivered")
    return 1 if report["unwaivered"] else 0


if __name__ == "__main__":
    raise SystemExit(_main())
