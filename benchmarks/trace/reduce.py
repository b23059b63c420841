""".xplane.pb -> device busy and idle time, time per operation, and what the
host was doing in each idle gap. Nothing but JAX reads the file
(``jax.profiler.ProfileData``); the arithmetic below works on plain
interval lists, so a test can check it on intervals made by hand as well as
on the small recorded trace kept beside the tests.

Conventions. A device is a plane named ``/device:TPU:<n>``; its operations
are the events of the line ``XLA Ops`` (nested where a loop or a fusion
holds others: busy time is the UNION of the intervals, and an operation's
own time is its length less its children's). The benchmark's host spans are
the events named ``bench:<what>`` on the host planes; ``bench:window`` spans
the measured window and gives its bounds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def label(name: str, width: int = 96) -> str:
    """A device event is named by its whole HLO instruction; keep the
    instruction's name, its opcode and its result's shape."""
    m = _HLO.match(name)
    if not m:
        return name[:width]
    shape = _LAYOUT.sub("", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:width]


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def clip(events: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def self_times(events: Sequence[Interval]) -> Dict[str, float]:
    """Own time per operation name: an event's length less the lengths of
    the events directly nested in it."""
    own = defaultdict(float)
    stack: List[list] = []           # [name, end, own]
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1]))):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            own[done[0]] += done[2]
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    for done in stack:
        own[done[0]] += done[2]
    return dict(own)


def label_gaps(idle: Sequence[Tuple[float, float]], spans: Sequence[Interval]
               ) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing: each gap goes, piece by
    piece, to the shortest host span covering that piece; what no span
    covers is ``(no span)``."""
    import numpy as np

    out = defaultdict(float)
    spans = sorted(spans, key=lambda sp: sp[2] - sp[1])      # shortest first
    starts = np.array([s for _, s, _ in spans], float)
    ends = np.array([e for _, _, e in spans], float)
    for lo, hi in idle:
        near = [spans[i] for i in np.flatnonzero((starts < hi) & (ends > lo))]
        cuts = sorted({lo, hi, *(t for _, s, e in near for t in (s, e)
                                 if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            name = next((n for n, s, e in near if s <= mid < e), "(no span)")
            out[name] += b - a
    return dict(out)


@dataclasses.dataclass
class TraceSummary:
    window_s: float                    # the traced window's length
    busy_s: float                      # device busy seconds, mean over devices
    n_devices: int
    op_seconds: Dict[str, float]       # own time by op name, mean over devices
    idle_seconds: Dict[str, float]     # idle time by host span, mean over devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top(self, table: Dict[str, float], n: int = 10) -> list:
        return [[label(k), v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def summarize(device_ops: Sequence[Sequence[Interval]],
              spans: Sequence[Interval],
              offsets_s: Tuple[float, float] = None) -> TraceSummary:
    """Reduce per-device operation intervals and the host's spans over the
    ``bench:window`` span, or over the part of it from ``offsets_s[0]`` to
    ``offsets_s[1]`` seconds after its start (a serving window sits
    between its pre-roll and its drain)."""
    if not device_ops or not any(device_ops):
        raise ValueError("the trace holds no device operation")
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    lo_dev = min(s for ops in device_ops for _, s, _ in ops)
    hi_dev = max(e for ops in device_ops for _, _, e in ops)
    if not wins or wins[0][0] >= hi_dev or wins[0][1] <= lo_dev:
        # never fall back to the device events' own extent: the idle share
        # would then be read over another window with no sign of it
        raise ValueError(
            f"no {WINDOW_SPAN!r} span on the device operations' clock "
            f"(spans {wins[:1]}, device operations {lo_dev}..{hi_dev} ns): "
            "the host's spans and the device's events are not aligned")
    lo, hi = wins[0]
    if offsets_s is not None:
        lo, hi = lo + offsets_s[0] * 1e9, lo + offsets_s[1] * 1e9
    inner = [sp for sp in clip(spans, lo, hi) if sp[0] != WINDOW_SPAN]
    busy, op_s, idle_s = 0.0, defaultdict(float), defaultdict(float)
    for ops in device_ops:
        ops = clip(ops, lo, hi)
        flat = [(s, e) for _, s, e in ops]
        busy += union_length(flat)
        for k, v in self_times(ops).items():
            op_s[k] += v
        for k, v in label_gaps(gaps(flat, lo, hi), inner).items():
            idle_s[k] += v
    n = len(device_ops)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n, n_devices=n,
        op_seconds={k: v * 1e-9 / n for k, v in op_s.items()},
        idle_seconds={k.replace(SPAN_PREFIX, "", 1): v * 1e-9 / n
                      for k, v in idle_s.items()})


def read_xplane(path: str):
    """(per-device operation intervals, host spans) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.append([
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return device_ops, spans


def reduce_file(path: str, offsets_s=None) -> TraceSummary:
    return summarize(*read_xplane(path), offsets_s=offsets_s)
