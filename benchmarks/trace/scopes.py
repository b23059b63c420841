"""Device time by the scopes the program named (``jax.named_scope``). A
scope reaches the optimized HLO in ``metadata={op_name="jit(tick)/.../
paged_read/gather"}`` (the backward pass carries it as
``transpose(jvp(loss))``), a device event is named after its instruction,
and ``obs["hlo_text"]`` is the timed program's text.

What the TPU's compiler leaves of the names (read in the cells' own
programs, PERF.md section 6, PR 24): a fusion carries ONE operation's
``op_name`` (a matrix product's, where it fuses one), the others' stay on
the instructions inside its fused computation; and the copies, casts and
bitcasts the compiler itself inserts carry none. So an instruction is IN a
scope when its own ``op_name`` holds the scope, or that of an instruction
it fuses does ("is, or fuses", as ``conv_share.train`` counts
convolutions), or, where neither it nor anything it fuses has a name at
all, when the instruction producing one of its operands is in the scope. A
fusion's time cannot be split, so one that fuses operations of two scopes
counts whole in both.

An event of another program (a prefill's ``fusion.12``) must not be joined
with the timed program's instruction of the same name: the join is on the
name, the opcode and the result's shape together (``reduce.label``).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

from benchmarks.trace import reduce

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*)$")
_HEAD = re.compile(r"^%?([\w.\-]+) = .*? [\w\-]+\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_WHOLE = 1 << 20          # label() without its cut to a table's width
_DEPTH = 4                # how far a nameless instruction looks upstream


def _key(instruction: str) -> str:
    return reduce.label(instruction, _WHOLE)


def _operands(text: str, start: int) -> List[str]:
    """Names referred to inside the parentheses that open at ``start``."""
    depth, i = 1, start
    while i < len(text) and depth:
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        i += 1
    return _REF.findall(text[start:i])


class Program:
    """The instructions of one optimized HLO module, by name."""

    def __init__(self, hlo_text: str):
        self.key: Dict[str, str] = {}        # name -> 'name opcode shape'
        self.own: Dict[str, str] = {}        # name -> its own op_name
        self.calls: Dict[str, str] = {}      # fusion -> fused computation
        self.operands: Dict[str, List[str]] = {}
        self.inside: Dict[str, List[str]] = {}   # computation -> op_names
        current = None
        for line in hlo_text.splitlines():
            if current is None:
                m = _COMPUTATION.match(line)
                if m:
                    current = m.group(1)
                    self.inside[current] = []
                continue
            if line.startswith("}"):
                current = None
                continue
            m = _INSTRUCTION.match(line)
            head = m and _HEAD.match(m.group(1))
            if not head:
                continue
            text, name = m.group(1), head.group(1)
            self.key[name] = _key(text)
            named = _OP_NAME.search(text)
            self.own[name] = named.group(1) if named else ""
            if named:
                self.inside[current].append(named.group(1))
            self.operands[name] = _operands(text, head.end())
            called = _CALLS.search(text)
            if called:
                self.calls[name] = called.group(1)

    def names_of(self, name: str) -> List[str]:
        """The instruction's own ``op_name`` and those it fuses."""
        own = [self.own[name]] if self.own.get(name) else []
        return own + self.inside.get(self.calls.get(name, ""), [])

    def in_scope(self, name: str, hit, depth: int = _DEPTH) -> bool:
        names = self.names_of(name)
        if names:
            return any(hit.search(n) for n in names)
        return depth > 0 and any(
            self.in_scope(op, hit, depth - 1)
            for op in self.operands.get(name, ()) if op in self.key)

    def keys_in(self, scope: str) -> set:
        """'name opcode shape' of every instruction in ``scope``."""
        hit = in_scope(scope)
        return {self.key[n] for n in self.key if self.in_scope(n, hit)}


def in_scope(scope: str):
    """Matches an ``op_name`` that holds ``scope`` as a whole component:
    ``.../loss/...`` and ``transpose(jvp(loss))/...``, not ``loss_fn``."""
    return re.compile(r"(?<![\w.\-])" + re.escape(scope) + r"(?![\w.\-])")


def scope_seconds(op_seconds: Dict[str, float], hlo_text: str,
                  scope: str) -> float:
    """Own seconds of the events whose instruction lies in ``scope``."""
    keys = Program(hlo_text).keys_in(scope)
    return sum(v for k, v in op_seconds.items() if _key(k) in keys)


def share(obs: dict, scope: str) -> Optional[float]:
    """Percent of the traced window's device own time inside ``scope``;
    None where the program names no such scope (or nothing ran in it)."""
    trace, text = obs.get("trace"), obs.get("hlo_text")
    if trace is None or not text:
        return None
    inside = scope_seconds(trace.op_seconds, text, scope)
    total = sum(trace.op_seconds.values())
    return 100.0 * inside / total if inside > 0 else None
