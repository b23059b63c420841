"""Static cost attribution: bucket a compiled step's HLO by op category.

The ledger answers "how long did the step take" (PR 2) and "how much of it
was communication" (PR 4) — but not "WHICH op category is eating it". The
XLA cost model's totals (``utils.telemetry.program_stats``) collapse the
whole program into one flops number; an MFU push needs the split: how much
of the model's arithmetic is matmul vs attention, how many bytes move
through collectives of each kind, and how much elementwise/fusion residue
rides along. This module walks the OPTIMIZED (post-fusion) HLO text of the
same executable the telemetry probe already lowered (``program_stats(...,
with_hlo=True)`` — one AOT lower for hbm/flops/attribution together) and
accumulates per-category flop and byte estimates:

* ``matmul``       — ``dot`` / ``convolution`` (and backend matmul
  custom-calls): flops from the contraction dims, exactly;
* ``attention``    — any op whose jax ``op_name`` metadata places it in an
  attention scope (the dots and softmax fusions of the attention block
  report here, not under matmul/fusion — flash-attention custom-calls
  included, though their inner flops are invisible to HLO);
* ``collective:*`` — all-reduce / all-gather / reduce-scatter /
  collective-permute / all-to-all, bytes = operand+result sizes (flops 0);
* ``elementwise``  — un-fused top-level ops (~1 flop per output element);
* ``fusion``       — fusion instructions: HBM bytes from their operand and
  result shapes (inner temporaries live in registers, so inner byte counts
  would be fiction), flops recursed from the fused computation so an
  embedded dot still lands in matmul/attention.

Estimates, not measurements: ``while`` bodies (lax.scan windows) are
counted ONCE like XLA's own cost model, custom-call kernels (Pallas) are
opaque, and elementwise flops are 1/element. The point is the SHARE
structure — which the ledger_report roofline section then compares against
measured ``device_s``/``comm_s``/MFU per step window. Pure stdlib: parsing
imports no jax, so canned HLO text attributes on a login host too.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

# dtype -> bytes per element (HLO shape literals: f32[8,32]{1,0})
_DTYPES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1,
    "token": 0, "opaque": 0,
}
# longest-first alternation so f8e4m3fn wins over f8e4m3; \b guards keep
# attribute text like devices=[1,2] from reading as a shape
_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(_DTYPES, key=len, reverse=True))
    + r")\[([0-9,]*)\]")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"\s*([A-Za-z][\w\-]*)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_SUBCOMP_RE = re.compile(r"(?:body|condition|true_computation|"
                         r"false_computation)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([0-9a-z?]+)_([0-9a-z?]+)->")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
# attention scopes: named attention modules/kernels, plus the bare einsum
# scopes of the score/value contractions (bqhd,bkhd->bhqk and its
# transpose carry 'bhqk' in the op_name path on every model here)
_ATTN_RE = re.compile(r"attn|attention|flash|bhqk", re.I)
_MATMUL_TARGET_RE = re.compile(r"matmul|dot|conv|gemm", re.I)

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
# zero-cost bookkeeping ops (and the -done halves of async pairs: the
# -start instruction carries the shapes once)
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id",
             "opt-barrier", "domain"}


def _dims(spec: str) -> int:
    n = 1
    for d in spec.split(","):
        if d:
            n *= int(d)
    return n


def _shapes_bytes(segment: str) -> float:
    return sum(_DTYPES[m.group(1)] * _dims(m.group(2))
               for m in _SHAPE_RE.finditer(segment))


def _split_output_shape(rest: str):
    """Split 'SHAPE opcode(...)...' into (shape segment, tail). Tuple
    shapes — '(f32[8]{0}, s32[]{})' — span to the matching paren."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:]
        return rest, ""
    i = rest.find(" ")
    return (rest, "") if i < 0 else (rest[:i], rest[i:])


def _operand_tokens(tail: str) -> List[str]:
    """The top-level comma-separated operands of 'opcode(a, b), attrs...'
    (``tail`` starts at the opening paren). Shape literals carry commas
    inside [] and {}, so split at bracket depth 1 only."""
    if not tail.startswith("("):
        return []
    out, depth, start = [], 0, 1
    for i, ch in enumerate(tail):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                out.append(tail[start:i])
                break
        elif ch == "," and depth == 1:
            out.append(tail[start:i])
            start = i + 1
    return [t.strip() for t in out if t.strip()]


class _Instr:
    __slots__ = ("opcode", "out_shape", "tail", "op_name", "operands")

    def __init__(self, opcode, out_shape, tail, op_name, operands):
        self.opcode = opcode
        self.out_shape = out_shape
        self.tail = tail          # everything after the opcode (operands+attrs)
        self.op_name = op_name
        self.operands = operands  # one shape segment per operand

    @property
    def io_shapes(self) -> str:
        """Result + operand shape text: what crosses the op's boundary."""
        return " ".join([self.out_shape, *self.operands])


def _parse_computations(hlo_text: str):
    """{computation name: [instructions]}, plus the ENTRY name."""
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    cur: Optional[List[_Instr]] = None
    shapes: Dict[str, str] = {}   # instruction name -> its result shape
    for raw in hlo_text.splitlines():
        m = _COMP_RE.match(raw)
        if m and "=" not in raw.split("(")[0]:
            name = m.group(2)
            cur = comps.setdefault(name, [])
            shapes = {}
            if m.group(1):
                entry = name
            continue
        if cur is None:
            continue
        if raw.strip() == "}":
            cur = None
            continue
        mi = _INSTR_RE.match(raw)
        if not mi:
            continue
        rest = mi.group(2)
        # metadata can quote arbitrary jax scope strings — take op_name
        # out first, then drop the block so it can't read as shapes
        mo = _OPNAME_RE.search(rest)
        op_name = mo.group(1) if mo else ""
        rest = re.sub(r"metadata=\{[^}]*\}", "", rest)
        shape_seg, tail = _split_output_shape(rest)
        mop = _OPCODE_RE.match(tail)
        if not mop:
            continue
        shapes[mi.group(1)] = shape_seg
        tail = tail[mop.end():]
        # operands print as 'f32[4,8]{1,0} %a' or, on current XLA, as the
        # bare name '%a' — resolve those against the defining instruction
        # (HLO is in def-before-use order within a computation)
        operands = [t if _SHAPE_RE.search(t) else shapes.get(t.lstrip("%"), "")
                    for t in _operand_tokens(tail)]
        cur.append(_Instr(mop.group(1), shape_seg, tail, op_name, operands))
    return comps, entry


def _dot_flops(instr: _Instr) -> float:
    """2 * |output| * K, K = product of the lhs contracting dim sizes."""
    out = sum(_dims(m.group(2)) for m in _SHAPE_RE.finditer(instr.out_shape))
    lhs = _SHAPE_RE.search(instr.operands[0]) if instr.operands else None
    mc = _LHS_CDIMS_RE.search(instr.tail)
    if lhs is None or mc is None:
        return 2.0 * out
    lhs_dims = [int(d) for d in lhs.group(2).split(",") if d]
    k = 1
    for i in (int(x) for x in mc.group(1).split(",") if x):
        if i < len(lhs_dims):
            k *= lhs_dims[i]
    return 2.0 * out * k


def _conv_flops(instr: _Instr) -> float:
    """2 * |output| * (kernel spatial x in-channels) — prod(kernel)/C_out,
    with C_out read off the dim_labels 'o' position."""
    out = sum(_dims(m.group(2)) for m in _SHAPE_RE.finditer(instr.out_shape))
    rhs = (_SHAPE_RE.search(instr.operands[1])
           if len(instr.operands) > 1 else None)
    ml = _DIM_LABELS_RE.search(instr.tail)
    if rhs is None or ml is None:
        return 2.0 * out
    kernel = [int(d) for d in rhs.group(2).split(",") if d]
    o_pos = ml.group(2).find("o")
    c_out = kernel[o_pos] if 0 <= o_pos < len(kernel) else 1
    import math
    return 2.0 * out * math.prod(kernel) / max(c_out, 1)


def _categorize(instr: _Instr) -> str:
    op = instr.opcode
    base = op[:-6] if op.endswith("-start") else op
    if base in _COLLECTIVES:
        return "collective:" + base
    if _ATTN_RE.search(instr.op_name):
        return "attention"
    if op in ("dot", "convolution"):
        return "matmul"
    if op == "custom-call":
        mt = _TARGET_RE.search(instr.tail)
        if mt and _MATMUL_TARGET_RE.search(mt.group(1)):
            return "matmul"
        return "custom-call"
    if op == "fusion":
        return "fusion"
    return "elementwise"


def _add(acc: dict, cat: str, flops: float, nbytes: float) -> None:
    b = acc.setdefault(cat, {"flops": 0.0, "bytes": 0.0, "count": 0})
    b["flops"] += flops
    b["bytes"] += nbytes
    b["count"] += 1


def _instr_flops(instr: _Instr) -> float:
    if instr.opcode == "dot":
        return _dot_flops(instr)
    if instr.opcode == "convolution":
        return _conv_flops(instr)
    if instr.opcode.startswith(tuple(_COLLECTIVES)) \
            or instr.opcode == "custom-call":
        return 0.0
    # ~1 flop per output element for everything else
    return float(sum(_dims(m.group(2))
                     for m in _SHAPE_RE.finditer(instr.out_shape)))


def _walk(name: str, comps: dict, acc: dict, fusion_cat: Optional[str],
          visiting: set) -> None:
    """Accumulate one computation's instructions into ``acc``. Inside a
    fusion (``fusion_cat`` set), only FLOPS accumulate — the fusion call
    site already charged the real HBM bytes — and residue inherits the
    fusion's category so an attention-scoped softmax fusion stays under
    attention."""
    if name in visiting or name not in comps:
        return  # unresolvable or (malformed) recursive reference
    visiting = visiting | {name}
    for instr in comps[name]:
        op = instr.opcode
        if op in _FREE_OPS or op.endswith("-done") or op.endswith("-update"):
            continue
        if op == "fusion":
            cat = _categorize(instr) if fusion_cat is None else fusion_cat
            if fusion_cat is None:
                # the fusion boundary is where HBM traffic happens
                _add(acc, cat, 0.0, _shapes_bytes(instr.io_shapes))
            mc = _CALLS_RE.search(instr.tail)
            if mc:
                _walk(mc.group(1), comps, acc, cat, visiting)
            continue
        if op in ("while", "conditional", "call"):
            # recurse into bodies/branches (counted ONCE, the cost-model
            # convention for scan windows); the call instruction's own
            # tuple shapes would double-count the carried state
            subs = _SUBCOMP_RE.findall(instr.tail) \
                + _CALLS_RE.findall(instr.tail)
            mb = _BRANCHES_RE.search(instr.tail)
            if mb:
                subs += re.findall(r"%?([\w.\-]+)", mb.group(1))
            for sub in subs:
                _walk(sub, comps, acc, fusion_cat, visiting)
            continue
        cat = _categorize(instr)
        if fusion_cat is not None and cat in ("elementwise", "custom-call"):
            cat = fusion_cat  # fusion residue
        nbytes = (0.0 if fusion_cat is not None
                  else _shapes_bytes(instr.io_shapes))
        _add(acc, cat, _instr_flops(instr), nbytes)


def cost_buckets(hlo_text: str) -> Dict[str, dict]:
    """{category: {'flops', 'bytes', 'count'}} for one optimized-HLO
    module (``compiled.as_text()`` / ``program_stats(..., with_hlo=True)
    ['hlo']``). Empty dict when the text has no parseable entry."""
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        # fall back to the largest computation (older printers may not
        # mark ENTRY on partial dumps)
        entry = max(comps, key=lambda k: len(comps[k]), default=None)
    acc: Dict[str, dict] = {}
    if entry is not None:
        _walk(entry, comps, acc, None, set())
    for b in acc.values():
        b["flops"] = round(b["flops"], 3)
        b["bytes"] = round(b["bytes"], 3)
    return acc


def bucket_totals(buckets: Dict[str, dict]) -> dict:
    """{'flops', 'bytes', 'collective_bytes'} rollup of cost_buckets()."""
    return {
        "flops": sum(b["flops"] for b in buckets.values()),
        "bytes": sum(b["bytes"] for b in buckets.values()),
        "collective_bytes": sum(b["bytes"] for c, b in buckets.items()
                                if c.startswith("collective:")),
    }


# -- device peaks (the roofline's denominators) ----------------------------

# HBM bandwidth GB/s per chip by device kind (public spec sheets; the
# compute-peak twin lives in utils.mfu.PEAK_TFLOPS)
PEAK_GBPS = (
    ("v6", 1640.0), ("trillium", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0), ("v5e", 819.0), ("v5litepod", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def effective_peak_gbps() -> tuple:
    """(peak_gbps, is_nominal): published HBM bandwidth of device 0, or the
    ``TPU_DIST_NOMINAL_PEAK_GBPS`` fallback (default 1.0) that keeps the
    roofline's memory bound non-null on CPU/virtual backends. An unlisted
    TPU raises (utils.mfu.lookup_peak)."""
    import os

    import jax

    from tpu_dist.utils.mfu import lookup_peak

    peak = lookup_peak(getattr(jax.devices()[0], "device_kind", ""),
                       PEAK_GBPS, "HBM GB/s")
    if peak:
        return peak, False
    return float(os.environ.get("TPU_DIST_NOMINAL_PEAK_GBPS", "1.0")), True


def emit_cost_model(ledger, program: str, hlo_text: str,
                    xla_flops=None) -> Optional[dict]:
    """Bucket ``hlo_text`` and emit the ``cost_model`` ledger event beside
    the engines' ``compile`` event (same one-lower probe). Returns the
    record, or None when the text yields no buckets (nothing to report).
    ``xla_flops`` carries the cost model's own total for cross-checking
    the attribution (the buckets' matmul flops should dominate it)."""
    buckets = cost_buckets(hlo_text)
    if not buckets:
        return None
    tot = bucket_totals(buckets)
    from tpu_dist.obs import effective_peak_tflops

    peak_tf, tf_nominal = effective_peak_tflops()
    peak_gb, gb_nominal = effective_peak_gbps()
    return ledger.emit(
        "cost_model", program=program, buckets=buckets,
        total_flops=tot["flops"], total_bytes=tot["bytes"],
        collective_bytes=tot["collective_bytes"], xla_flops=xla_flops,
        peak_tflops=peak_tf, peak_gbps=peak_gb,
        peak_is_nominal=tf_nominal or gb_nominal)
