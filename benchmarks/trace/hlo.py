"""Join a trace with the compiled program's own text: a device event is
named after its HLO instruction, and on the TPU a convolution lives inside
a fusion whose name says nothing. ``instructions_holding`` reads the
optimized HLO and gives the names of the instructions that are, or whose
fused computation holds, an operation of the wanted opcode."""

from __future__ import annotations

import re
from typing import Set

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def instructions_holding(hlo_text: str, opcode: str) -> Set[str]:
    """Names of the instructions of ``hlo_text`` that are an ``opcode``
    operation or a fusion over a computation that holds one."""
    call = re.compile(r"\s" + re.escape(opcode) + r"\(")
    holding, direct, fusions = set(), set(), []
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        if call.search(line):
            holding.add(current)
            direct.add(m.group(1))
        called = _CALLS.search(line)
        if called and " fusion(" in line:
            fusions.append((m.group(1), called.group(1)))
    return direct | {name for name, comp in fusions if comp in holding}


def event_instruction(event_name: str) -> str:
    """'%fusion.12 = ...' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()
